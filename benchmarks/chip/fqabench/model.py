"""The program's parameter tree, drawn by role from the seed.

Every program module (``programs/<name>.py``) may draw its parameters
here: the program states its parameter specs, and each leaf is filled by
:func:`fqabench.weights.draw`, keyed by the leaf's role and by its layer
counted over the whole model (the stages ``s0_*``, ``s1_*``, ... of the
program's tree follow one another), so a reference that draws one layer
at a time gets the same bits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import weights


def _first_layer(names, stages) -> int:
    """The model-wide index of the first layer of the stage that holds a
    leaf (0 for a leaf outside the stages)."""
    if len(names) < 2 or names[0] != "stages":
        return 0
    i = int(names[1][1:].split("_")[0])
    return sum(st.n_layers for st in stages[:i])


def draw_params(cfg, seed: int, extra=None):
    """Every parameter of ``cfg``, drawn from ``seed`` in one jitted
    program on the default device, in the served dtype; ``extra`` is the
    program module's ``ROLES``, the rules of roles beyond the dense ones."""
    from repro.models import param_specs
    from repro.models.common import P

    specs = param_specs(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))
    dtype = jnp.dtype(cfg.param_dtype)

    def build(key):
        leaves = []
        for path, spec in flat:
            names = [str(getattr(p, "key", p)) for p in path]
            role = weights.role_of(path)
            if spec.axes and spec.axes[0] == "layers":
                first = _first_layer(names, cfg.stages)
                inner = spec.shape[1:]
                w = jax.vmap(lambda l, r=role, s=inner:
                             weights.draw(key, r, first + l, s, extra))(
                    jnp.arange(spec.shape[0]))
            else:
                w = weights.draw(key, role, 0, spec.shape, extra)
            leaves.append(w.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(weights.base_key(seed))
