"""Fused float-in/float-out PPA activation kernel (one ``pallas_call``).

The deployment hot path used to run as unfused jnp pre/post-processing
around the integer Pallas kernel: quantize, table-eval, dequantize,
symmetry-restore and the silu/gelu self-gating each made a separate pass
over the activation tensor.  This kernel performs the whole pipeline on one
(block_m, 128) tile while it sits in VMEM:

    quantize -> range-reduce (symmetry) -> segment-select -> Horner
             -> dequantize -> saturation -> [optional x * T(x) gating]

The integer stage is the shared kernel body (:mod:`repro.kernels.body`)
driven by the table's :class:`~repro.core.datapath.DatapathPlan`; the float
conditioning replays ``kernels.ops.ppa_apply`` operation-for-operation in
float32, so the fused path is bit-identical to the unfused backends (tests
assert exact equality, gated and ungated, across the NAF zoo).

Fusing non-uniform piecewise activation evaluation into the surrounding
compute is the Flex-SFU / DAPA play (PAPERS.md): the activation becomes one
VMEM-resident pass instead of five HBM round trips.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.datapath import DatapathPlan

from .body import ppa_eval_block, resolve_interpret, smem_operands
from .ppa import DEFAULT_BLOCK, default_block, pad_to_tiles

__all__ = ["ppa_fused_2d", "ppa_fused_apply", "fused_kernel_statics"]


def _fused_kernel(x_ref, starts_ref, coef_ref, out_ref, *,
                  plan: DatapathPlan, num_segments: int, lo: int, hi: int,
                  symmetry: Optional[str], sat_hi: Optional[float],
                  sat_identity: bool, gate: bool):
    """One tile of the full float->PPA->float pipeline.

    Float conditioning mirrors ``ops.ppa_apply`` exactly (same ops, same
    order, float32 throughout) so results are bit-identical to the unfused
    composition; the statics make every branch compile-time.
    """
    x0 = x_ref[...].astype(jnp.float32)

    # range reduction: evaluate |x|, remember the sign for reconstruction
    xf = jnp.abs(x0) if symmetry else x0

    # quantize to the input grid (round-half-away, matching to_fixed)
    scale_in = float(1 << plan.w_in)
    x_int = jnp.floor(jnp.abs(xf) * scale_in + 0.5).astype(jnp.int32)
    x_int = jnp.where(xf < 0, -x_int, x_int)  # xf >= 0 under symmetry anyway

    oob_hi = x_int >= hi
    x_int = jnp.clip(x_int, lo, hi - 1)

    y_int = ppa_eval_block(x_int, starts_ref, coef_ref, plan,
                           num_segments=num_segments)
    y = y_int.astype(jnp.float32) * (1.0 / (1 << plan.w_out))

    # saturation outside the fitted interval
    if sat_identity:
        y = jnp.where(oob_hi, xf, y)
    elif sat_hi is not None:
        y = jnp.where(oob_hi, jnp.float32(sat_hi), y)

    # symmetry reconstruction
    neg = x0 < 0
    if symmetry == "odd":
        y = jnp.where(neg, -y, y)
    elif symmetry == "sigmoid":
        y = jnp.where(neg, 1.0 - y, y)
    elif symmetry == "minus_x":
        y = jnp.where(neg, y - xf, y)

    if gate:                       # silu/gelu self-gating: x * T(x)
        y = x0 * y
    out_ref[...] = y


def fused_kernel_statics(tc) -> dict:
    """The compile-time scalars of the fused pipeline, derived from a
    packed :class:`~repro.kernels.ops.TableConsts`."""
    return dict(plan=tc.plan, num_segments=tc.num_segments, lo=tc.lo,
                hi=tc.hi, symmetry=tc.symmetry, sat_hi=tc.sat_hi,
                sat_identity=tc.sat_identity)


def ppa_fused_2d(
    xf: jax.Array,
    starts: jax.Array,
    coefs: jax.Array,
    *,
    gate: bool = False,
    block: Tuple[int, int] = DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
    **statics,
) -> jax.Array:
    """Run the fused pipeline on a 2D float32 array (pre-padded to tiles).

    ``statics`` are the scalars from :func:`fused_kernel_statics`.
    """
    m, n = xf.shape
    grid = (m // block[0], n // block[1])
    kernel = functools.partial(_fused_kernel, gate=gate, **statics)
    table_specs, table_args = smem_operands(starts, coefs)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i, j: (i, j)), *table_specs],
        out_specs=pl.BlockSpec(block, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(xf.astype(jnp.float32), *table_args)


def ppa_fused_apply(tc, xf: jax.Array, *, gate: bool = False,
                    block: "Tuple[int, int] | None" = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Any-shape adapter: flatten + pad to the tile grid, run the fused
    kernel, unpad.  float32 in, float32 out.

    ``block=None`` resolves the process default (autotuner-overridable,
    :func:`repro.kernels.ppa.default_block`); outputs are block-shape
    independent either way.
    """
    if block is None:
        block = default_block()
    shape = xf.shape
    flat = xf.reshape(-1)
    n = flat.shape[0]
    x2, blk = pad_to_tiles(flat, block[0], block[1])
    out = ppa_fused_2d(x2, tc.starts, tc.coefs, gate=gate, block=blk,
                       interpret=interpret, **fused_kernel_statics(tc))
    return out.reshape(-1)[:n].reshape(shape)
