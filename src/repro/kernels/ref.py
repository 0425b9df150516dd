"""Pure-jnp oracle for the PPA kernels (and the default CPU execution path).

The Horner chain is literally ``core.datapath.horner_body`` (the same code
object the numpy golden model runs, here under jnp int32), driven by a
:class:`~repro.core.datapath.DatapathPlan`; only the segment select differs
from the Pallas kernels (a searchsorted gather instead of the comparator
sweep or the blocked bisection).  Bit-identical to kernels/ppa.py and to the numpy golden model
(core.schemes.eval_table_int); tests assert exact integer equality among
all three.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.datapath import DatapathPlan, horner_body


def horner_int(sel: jax.Array, x_int: jax.Array, plan: DatapathPlan
               ) -> jax.Array:
    """The fixed-point Horner datapath given pre-selected coefficients
    ``sel`` of shape (..., n+1)."""
    x = x_int.astype(jnp.int32)
    planes = [sel[..., i] for i in range(plan.order + 1)]
    return horner_body(plan, planes, x)


def ppa_eval_ref(x_int: jax.Array, starts: jax.Array, coefs: jax.Array,
                 plan: DatapathPlan) -> jax.Array:
    """Evaluate the PPA datapath on int32 inputs of any shape."""
    x = x_int.astype(jnp.int32)
    idx = jnp.clip(
        jnp.searchsorted(starts.astype(jnp.int32), x, side="right") - 1,
        0, starts.shape[0] - 1)
    sel = coefs.astype(jnp.int32)[idx]          # (..., n+1)
    return horner_int(sel, x, plan)
