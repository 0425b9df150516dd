"""Fused row-softmax Pallas kernel with PPA exp2 (MBS/TEA-S lineage).

softmax(x)_j = e_j / sum_j e_j  with  e_j = 2**k_j * T(f_j),
  s_j = (x_j - max(x)) * log2(e),  k_j = floor(s_j),  f_j = s_j - k_j.

Only the fractional power 2**f goes through the fixed-point PPA datapath
(the paper's machinery); the 2**k scale and the final normalisation stay in
float (exact ldexp / one division per row) — exactly the split a hardware
softmax unit makes between the NAF core and the float post-scaler.

The integer stage is the shared kernel body (segment select chosen by S +
``core.datapath.horner_body``) driven by the table's
:class:`~repro.core.datapath.DatapathPlan` — including the ``round_mults``
half-ULP add, which a previous hand-rolled copy of the Horner chain here
silently dropped (regression-tested in tests/test_backend_parity.py).

Tiling: one block holds ``block_m`` full rows (block shape (block_m, N));
row reductions stay inside the block so there is no cross-block revisit.
For attention-sized rows (N <= 32k f32 = 128 KiB/row) block_m=8 keeps the
working set ~1 MiB — well inside VMEM with double buffering.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.datapath import DatapathPlan

from .body import (kernel_name, ppa_eval_block, resolve_interpret,
                   table_operands)
from .ops import TableConsts

__all__ = ["softmax_ppa_2d"]

_LOG2E = math.log2(math.e)
_CLAMP = -24.0  # 2^-24 is below every table's output ULP


def _softmax_kernel(x_ref, starts_ref, coef_ref, out_ref, *,
                    plan: DatapathPlan, num_segments: int, valid_n: int):
    x = x_ref[...].astype(jnp.float32)
    n = x.shape[-1]
    if valid_n < n:  # tail padding is masked out of max & sum
        col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(col < valid_n, x, -jnp.inf)

    m = jnp.max(x, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    s = jnp.maximum((x - m) * np.float32(_LOG2E), np.float32(_CLAMP))
    k = jnp.floor(s)
    f = s - k                                              # in [0, 1)
    f_int = jnp.floor(f * np.float32(1 << plan.w_in) + 0.5).astype(jnp.int32)
    f_int = jnp.clip(f_int, 0, (1 << plan.w_in) - 1)

    y_int = ppa_eval_block(f_int, starts_ref, coef_ref, plan,
                           num_segments=num_segments)

    e = y_int.astype(jnp.float32) * np.float32(1.0 / (1 << plan.w_out))
    e = e * jnp.exp2(k)                                    # exact scale
    if valid_n < n:
        e = jnp.where(col < valid_n, e, 0.0)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    out_ref[...] = e / jnp.maximum(denom, 1e-30)


def softmax_ppa_2d(x: jax.Array, tc: TableConsts, *, block_m: int = 8,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Row softmax over the last axis of a 2D float array via PPA exp2."""
    assert tc.naf == "exp2_frac", tc.naf
    m, n = x.shape
    pad_m = (-m) % block_m
    pad_n = (-n) % 128
    xp = jnp.pad(x, ((0, pad_m), (0, pad_n)), constant_values=-jnp.inf)
    mp, np_ = xp.shape

    plan = tc.plan
    kernel = functools.partial(_softmax_kernel, plan=plan,
                               num_segments=tc.num_segments, valid_n=n)

    table_specs, table_args = table_operands(tc.starts, tc.coefs)
    out = pl.pallas_call(
        kernel,
        grid=(mp // block_m,),
        in_specs=[pl.BlockSpec((block_m, np_), lambda i: (i, 0)),
                  *table_specs],
        out_specs=pl.BlockSpec((block_m, np_), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=resolve_interpret(interpret),
        name=kernel_name("ppa_softmax", tc.num_segments),
    )(xp.astype(jnp.float32), *table_args)
    return out[:m, :n].astype(x.dtype)
