"""The one in-kernel PPA evaluation body shared by every Pallas kernel.

Hardware mapping of the paper's computation unit (DESIGN.md §3/§5):

  * index generator (s-1 comparators) -> :func:`select_coeffs_sweep`, a
    compare-select sweep over the sorted segment-start vector held in SMEM
    (scalar memory: every comparator reads one start and broadcasts it).
    Because starts are sorted ascending, the running
    ``where(x >= starts[s], row_s, acc)`` sweep leaves exactly the last
    matching row selected — the vectorised analogue of the parallel
    comparator + priority encoder, with no per-element dynamic addressing
    (which the TPU vector unit cannot do efficiently).
  * truncating multipliers / concat adders -> ``core.datapath.horner_body``
    driven by a :class:`~repro.core.datapath.DatapathPlan`; the shift
    constants are compile-time ints baked into the kernel, and the body is
    the *same code object* the numpy golden model and the jnp reference op
    execute, so the three paths cannot drift apart.

Every Pallas kernel in this package (kernels/ppa.py, kernels/softmax_ppa.py,
kernels/fused.py) calls :func:`ppa_eval_block` for its integer datapath
stage; nothing in this package derives a shift amount on its own.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.datapath import DatapathPlan, horner_body

__all__ = ["select_coeffs_sweep", "ppa_eval_block", "smem_operands",
           "resolve_interpret"]


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Kernels run in Pallas interpret mode only where the platform is the
    CPU; an explicit bool wins (compiling for a described TPU from a CPU
    host passes ``False``)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def smem_operands(starts, coefs):
    """The table operands as the kernels take them: the (S,) int32 starts
    and the (S, order+1) coefficients flattened to 1-D, each one whole-array
    SMEM block (1-D keeps SMEM free of a padded minor dimension)."""
    spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return ([spec, spec],
            (starts.astype(jnp.int32), coefs.astype(jnp.int32).reshape(-1)))


def select_coeffs_sweep(x_int, starts_ref, coef_ref, *, num_segments: int,
                        order: int) -> List:
    """Comparator-sweep segment select: returns the ``order + 1`` coefficient
    planes (a_1..a_n, b) selected per element of ``x_int``.

    ``starts_ref`` is the (S,) start vector and ``coef_ref`` the coefficient
    ROM flattened row-major to (S * (order + 1),) — both 1-D SMEM refs in
    the kernels (see :func:`smem_operands`), read by scalar index only.
    """
    k = order + 1
    sel = [jnp.full(x_int.shape, coef_ref[c], dtype=jnp.int32)
           for c in range(k)]
    for s in range(1, num_segments):
        ge = x_int >= starts_ref[s]
        for c in range(k):
            sel[c] = jnp.where(ge, coef_ref[s * k + c], sel[c])
    return sel


def ppa_eval_block(x_int, starts_ref, coef_ref, plan: DatapathPlan, *,
                   num_segments: int):
    """segment-select sweep + fixed-point Horner chain for one tile."""
    sel = select_coeffs_sweep(x_int, starts_ref, coef_ref,
                              num_segments=num_segments, order=plan.order)
    return horner_body(plan, sel, x_int)
