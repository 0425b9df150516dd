"""The system under test, built from a configuration file.

This module and :mod:`fqabench.harness` are the only places that import
the program (``repro``).  The program's own configuration of the named
architecture supplies everything the file does not state (attention
path, remat, and the activation backend unless the file pins one), so a
change to those defaults is measured; every size the file states
overrides it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import weights


def program_cfg(conf: dict):
    """``repro`` ModelCfg of a dense GQA decoder as ``conf`` states it."""
    from repro.configs import get_config
    from repro.models import StageCfg

    base = get_config(conf["program_arch"])
    if base.family != "dense" or any(s.kind != "dec" or s.moe or s.window
                                     for s in base.stages):
        raise ValueError(f"{conf['program_arch']}: not a dense decoder")
    return base.replace(
        arch=conf["name"],
        d_model=conf["hidden_size"], n_q=conf["num_attention_heads"],
        n_kv=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        stages=(StageCfg("dec", conf["num_hidden_layers"]),),
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        act_impl=conf["act_impl"],
        act_backend=conf.get("act_backend", base.act_backend),
        param_dtype=conf["torch_dtype"],
        compute_dtype=conf["torch_dtype"])


def program_params(cfg, seed: int):
    """Every parameter of ``cfg``, drawn from ``seed`` in one jitted
    program on the default device, in the served dtype."""
    from repro.models import param_specs
    from repro.models.common import P

    specs = param_specs(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))
    dtype = jnp.dtype(cfg.param_dtype)

    def build(key):
        leaves = []
        for path, spec in flat:
            role = weights.role_of(path)
            if spec.axes and spec.axes[0] == "layers":
                inner = spec.shape[1:]
                w = jax.vmap(lambda l, r=role, s=inner:
                             weights.draw(key, r, l, s))(
                    jnp.arange(spec.shape[0]))
            else:
                w = weights.draw(key, role, 0, spec.shape)
            leaves.append(w.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(weights.base_key(seed))
