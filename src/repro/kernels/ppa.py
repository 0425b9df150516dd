"""Pallas TPU kernel for batched fixed-point PPA activation evaluation.

The kernel body is the shared one from :mod:`repro.kernels.body` (comparator
sweep + ``core.datapath.horner_body``); every shift/alignment constant comes
from a :class:`~repro.core.datapath.DatapathPlan` — this module derives
nothing on its own.

Block layout: x is tiled (block_m, 128) int32 — the minor dimension matches
the 128-lane VPU; block_m=256 keeps in+out VMEM traffic at 256 KiB/block,
far below the ~16 MiB v5e VMEM budget, leaving room for double buffering.
The segment starts and the flattened (S * (n+1),) int32 coefficient ROM sit
whole in SMEM (< 8 KiB for every deployment table): the comparator sweep
reads them one scalar at a time.
"""

from __future__ import annotations

import functools
from typing import TYPE_CHECKING, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.datapath import DatapathPlan, FWLConfig

from .body import ppa_eval_block, resolve_interpret, smem_operands

if TYPE_CHECKING:  # avoid a module-level kernels -> core.schemes import edge
    from repro.core.schemes import PPATable

DEFAULT_BLOCK = (256, 128)

#: process-wide active block shape, overridable by the per-device
#: autotuner (:mod:`repro.tune`).  Callers that pass ``block=None`` (the
#: backend-registry paths in :mod:`repro.kernels.ops` and the fused
#: kernels) resolve through :func:`default_block`; an explicit ``block``
#: argument always wins.  Must be set *before* the first trace of a jitted
#: caller — block shape is a trace-time static.
_active_block: Tuple[int, int] = DEFAULT_BLOCK


def default_block() -> Tuple[int, int]:
    """The block shape used when a caller does not pick one explicitly."""
    return _active_block


def set_default_block(block: Optional[Tuple[int, int]]) -> Tuple[int, int]:
    """Override the process default block shape (None resets).

    A pure execution knob: padding/slicing keeps kernel outputs
    block-shape-independent (asserted by the kernel parity suite), so the
    autotuner may apply a tuned shape without touching any artifact.
    """
    global _active_block
    if block is None:
        _active_block = DEFAULT_BLOCK
    else:
        bm, bn = int(block[0]), int(block[1])
        if bm <= 0 or bn <= 0 or bn % 128:
            raise ValueError(f"invalid block {block!r}: want (m>0, n%128==0)")
        _active_block = (bm, bn)
    return _active_block


PlanLike = Union[DatapathPlan, FWLConfig]


def as_plan(plan: PlanLike) -> DatapathPlan:
    """Accept a DatapathPlan or derive one from an FWLConfig (the only
    derivation entrypoint, ``DatapathPlan.from_config``)."""
    if isinstance(plan, DatapathPlan):
        return plan
    return DatapathPlan.from_config(plan)


def _ppa_kernel(x_ref, starts_ref, coef_ref, out_ref, *, plan: DatapathPlan,
                num_segments: int):
    """One (block_m, 128) tile: select coefficients, run the Horner chain."""
    out_ref[...] = ppa_eval_block(x_ref[...], starts_ref, coef_ref, plan,
                                  num_segments=num_segments)


def ppa_eval_2d(
    x_int: jax.Array,
    starts: jax.Array,
    coefs: jax.Array,
    plan: PlanLike,
    *,
    block: Tuple[int, int] = DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Evaluate the PPA datapath on a 2D int32 array (pre-padded).

    Args:
      x_int: (M, N) int32, FWL plan.w_in; M % block[0] == 0,
        N % block[1] == 0.
      starts: (S,) int32 sorted segment starts (FWL plan.w_in).
      coefs: (S, n+1) int32 — columns a_1..a_n then b.
      plan: the DatapathPlan (or the FWLConfig to derive it from).
      interpret: None derives it from the platform (see
        :func:`~repro.kernels.body.resolve_interpret`).
    """
    plan = as_plan(plan)
    m, n = x_int.shape
    s = starts.shape[0]
    grid = (m // block[0], n // block[1])
    kernel = functools.partial(_ppa_kernel, plan=plan, num_segments=s)
    table_specs, table_args = smem_operands(starts, coefs)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i, j: (i, j)), *table_specs],
        out_specs=pl.BlockSpec(block, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(x_int.astype(jnp.int32), *table_args)


def pad_to_tiles(flat: jax.Array, block_m: int, block_n: int
                 ) -> Tuple[jax.Array, Tuple[int, int]]:
    """Zero-pad a flat array onto the (block_m, block_n) tile grid, growing
    block_m from 8 up to ``block_m`` while the row count stays divisible.
    Returns (x2d, (rows_block, block_n))."""
    n = flat.shape[0]
    bm, bn = 8, block_n
    pad = (-n) % (bm * bn)
    flat = jnp.pad(flat, (0, pad))
    x2 = flat.reshape(-1, bn)
    rows = x2.shape[0]
    while bm < block_m and rows % (bm * 2) == 0:
        bm *= 2
    return x2, (bm, bn)


def table_kernel_args(table: "PPATable"):
    """Derive the kernel operands straight from a compiled table artifact:
    (starts, coefs, plan)."""
    starts = jnp.asarray(np.asarray(table.starts_int), jnp.int32)
    coefs = jnp.asarray(
        np.concatenate([np.asarray(table.a_int),
                        np.asarray(table.b_int)[:, None]], axis=1), jnp.int32)
    return starts, coefs, DatapathPlan.from_config(table.cfg)


def ppa_eval_table(
    table: "PPATable",
    x_int: jax.Array,
    *,
    block: Tuple[int, int] = DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Evaluate a :class:`PPATable` artifact on integer inputs of any shape.

    The adapter between the store's artifact and the Pallas kernel: segment
    starts, the coefficient ROM and the DatapathPlan are derived from the
    table, and the input is flattened + zero-padded to the tile grid
    (padding lanes are evaluated and discarded).  Bit-identical to the
    numpy golden model ``core.schemes.eval_table_int``.
    """
    starts, coefs, plan = table_kernel_args(table)
    x = jnp.asarray(x_int, jnp.int32)
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    x2, blk = pad_to_tiles(flat, block[0], block[1])
    out = ppa_eval_2d(x2, starts, coefs, plan, block=blk,
                      interpret=interpret)
    return out.reshape(-1)[:n].reshape(shape)
