"""Model-facing jit'd PPA activation ops.

This is the bridge between the compiled :class:`~repro.core.schemes.PPATable`
artifact (the paper's deployable result) and the JAX model zoo: float tensors
in, float tensors out, with the fixed-point datapath bit-exact in the middle.

Pieces:

* ``TableConsts``    — the table packed as jnp arrays (device constants),
  plus the table's :class:`~repro.core.datapath.DatapathPlan`.
* ``ppa_apply``      — quantize -> range-reduce -> datapath -> dequantize,
  with symmetry handling (odd / sigmoid) and saturation outside the fitted
  interval, exactly as a hardware NAF unit would be deployed in front of an
  accelerator's vector lanes.
* ``ppa_gate``       — the gated form ``x * T(x)`` (silu = x * sigmoid(x),
  gelu = x * Phi(x)); on the fused backend the gating multiply happens
  inside the kernel, on every other backend it is the same float32 multiply
  applied outside — bit-identical either way.
* ``ppa_act`` / ``ppa_gate_act`` — custom_vjp wrappers: the forward pass is
  the PPA datapath, the backward pass is the *exact* derivative of the
  target NAF (straight-through estimator — standard QAT practice, and the
  only sound choice since the piecewise datapath has zero/undefined
  derivatives at segment boundaries).
* ``ppa_softmax``    — softmax whose exp is computed via the ``exp2_frac``
  table: exp(x) = 2**(x*log2e) = 2**k * table(frac), with the power-of-two
  scale applied exactly in float (ldexp is exact).

Execution path selection goes through the **backend registry**
(:func:`register_backend` / :func:`available_backends`):

  ref           pure jnp searchsorted + shared Horner body
                (paper-faithful; runs everywhere) — the default
  lut_value     one gather; the PPA compile is the LUT generator
  lut_index     gather the segment index, keep the Horner datapath
  pallas        the tiled int32 TPU kernel (kernels/ppa.py)
  pallas_fused  the fused float->PPA->float kernel (kernels/fused.py):
                quantize, symmetry, segment select, Horner, dequantize,
                saturation and the optional self-gating in ONE pallas_call

The Pallas backends compile for the TPU, and run the same kernel in
interpret mode where the platform is the CPU.  All backends are
bit-identical; tests assert exact equality.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.datapath import DatapathPlan, FWLConfig
from repro.core.functions import get_naf
from repro.core.schemes import PPATable

from .fused import ppa_fused_apply
from .ppa import default_block, pad_to_tiles, ppa_eval_2d
from .ref import horner_int, ppa_eval_ref

__all__ = ["TableConsts", "pack_table", "ppa_apply", "ppa_gate", "ppa_act",
           "ppa_gate_act", "ppa_softmax", "make_ppa_fn", "Backend",
           "register_backend", "get_backend", "available_backends"]


@dataclasses.dataclass(frozen=True)
class TableConsts:
    """A PPATable packed for device execution (hashable static part +
    jnp array constants that become XLA constants under jit)."""

    naf: str
    interval: Tuple[float, float]
    w_in: int
    w_out: int
    w_a: Tuple[int, ...]
    w_o: Tuple[int, ...]
    w_b: int
    round_mults: bool
    symmetry: Optional[str]
    sat_hi: Optional[float]
    sat_identity: bool
    num_segments: int
    # array leaves (not part of __hash__/__eq__ via compare=False)
    starts: jax.Array = dataclasses.field(compare=False)
    coefs: jax.Array = dataclasses.field(compare=False)
    # beyond-paper TPU deployment modes (bit-exact by construction):
    #   idx_lut[x - lo]  -> segment index   (kills the searchsorted loop)
    #   val_lut[x - lo]  -> datapath output (one gather; the PPA table is
    #                       the *compiler* for the LUT, per DESIGN.md §3)
    idx_lut: jax.Array = dataclasses.field(compare=False, default=None)
    val_lut: jax.Array = dataclasses.field(compare=False, default=None)
    lo: int = 0                 # integer interval [lo, hi) at FWL w_in
    hi: int = 0

    @property
    def fwl_config(self) -> FWLConfig:
        return FWLConfig(w_in=self.w_in, w_out=self.w_out, w_a=self.w_a,
                         w_o=self.w_o, w_b=self.w_b,
                         round_mults=self.round_mults)

    @property
    def plan(self) -> DatapathPlan:
        """The shift/alignment constants every backend executes with —
        derived in exactly one place (DatapathPlan.from_config)."""
        return DatapathPlan.from_config(self.fwl_config)


def pack_table(table: PPATable) -> TableConsts:
    from repro.core.schemes import eval_table_int

    # breakpoint layout contract: the comparator sweep and the searchsorted
    # index LUT below both require strictly increasing starts — holds for
    # uniform and non-uniform segmenters, and guards hand-built tables.
    table.validate()
    spec = get_naf(table.naf)
    coefs = np.concatenate([table.a_int, table.b_int[:, None]], axis=1)
    # int32 datapath headroom: exact per-segment abstract interpretation
    # (repro.analysis.certify) replaces the seed-era |coef|max * x_max
    # heuristic, which both under-detected (order>=2 concat-add / up-shift
    # growth past the first product) and over-rejected (segment-local
    # coefficient/input ranges are far tighter than the global product).
    from repro.analysis.certify import certify_table
    cert = certify_table(table)
    if not cert.ok:
        raise ValueError(
            f"table {table.naf} overflows the int32 datapath: "
            + "; ".join(v.describe() for v in cert.violations))

    # LUT deployment modes: the whole fixed-point input domain is small
    # (<= span * 2^w_in entries), so both the segment index and the full
    # datapath output can be tabulated bit-exactly at pack time.
    lo = int(math.ceil(table.interval[0] * (1 << table.cfg.w_in) - 1e-12))
    hi = int(math.ceil(table.interval[1] * (1 << table.cfg.w_in) - 1e-12))
    grid = np.arange(lo, hi, dtype=np.int64)
    idx = np.clip(np.searchsorted(table.starts_int, grid, side="right") - 1,
                  0, table.num_segments - 1)
    vals = eval_table_int(table, grid)

    return TableConsts(
        naf=table.naf, interval=tuple(table.interval),
        w_in=table.cfg.w_in, w_out=table.cfg.w_out,
        w_a=tuple(table.cfg.w_a), w_o=tuple(table.cfg.w_o),
        w_b=table.cfg.w_b, round_mults=table.cfg.round_mults,
        symmetry=spec.symmetry, sat_hi=spec.sat_hi,
        sat_identity=spec.sat_identity,
        num_segments=table.num_segments,
        starts=jnp.asarray(table.starts_int, dtype=jnp.int32),
        coefs=jnp.asarray(coefs, dtype=jnp.int32),
        idx_lut=jnp.asarray(idx, dtype=jnp.int32),
        val_lut=jnp.asarray(vals, dtype=jnp.int32),
        lo=lo, hi=hi)


# --------------------------------------------------------------------------
# backend registry
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Backend:
    """One execution path for a packed table.

    Exactly one of the two hooks is set:
      eval_int(tc, x_int) -> y_int   integer datapath only; the generic
                                     float conditioning in _apply_f32 wraps
                                     it (quantize/symmetry/saturation/gate).
      apply(tc, xf, gate) -> y_f32   the whole float->float pipeline
                                     (fused kernels own their conditioning).
    """

    name: str
    eval_int: Optional[Callable] = None
    apply: Optional[Callable] = None
    doc: str = ""


_BACKENDS: Dict[str, Backend] = {}


def register_backend(name: str, *, eval_int: Optional[Callable] = None,
                     apply: Optional[Callable] = None, doc: str = "") -> None:
    """Register an execution backend (see docs/ARCHITECTURE.md §"adding a
    backend").  Re-registering a name overwrites it."""
    if (eval_int is None) == (apply is None):
        raise ValueError("exactly one of eval_int/apply must be given")
    _BACKENDS[name] = Backend(name, eval_int=eval_int, apply=apply, doc=doc)


def get_backend(name: str) -> Backend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; "
                         f"available: {available_backends()}") from None


def available_backends() -> List[str]:
    return sorted(_BACKENDS)


def _eval_ref(tc: TableConsts, x_int: jax.Array) -> jax.Array:
    return ppa_eval_ref(x_int, tc.starts, tc.coefs, tc.plan)


def _eval_lut_value(tc: TableConsts, x_int: jax.Array) -> jax.Array:
    # one gather; the PPA compile is the LUT generator (bit-exact)
    return jnp.take(tc.val_lut, x_int - tc.lo, axis=0)


def _eval_lut_index(tc: TableConsts, x_int: jax.Array) -> jax.Array:
    # keep the Horner datapath, replace the segment search by a gather
    idx = jnp.take(tc.idx_lut, x_int - tc.lo, axis=0)
    return horner_int(tc.coefs.astype(jnp.int32)[idx], x_int, tc.plan)


def _eval_pallas(tc: TableConsts, x_int: jax.Array) -> jax.Array:
    shape = x_int.shape
    flat = x_int.reshape(-1)
    n = flat.shape[0]
    bm, bn = default_block()
    x2, blk = pad_to_tiles(flat, bm, bn)
    out = ppa_eval_2d(x2, tc.starts, tc.coefs, tc.plan, block=blk)
    return out.reshape(-1)[:n].reshape(shape)


def _apply_fused(tc: TableConsts, xf: jax.Array, gate: bool) -> jax.Array:
    return ppa_fused_apply(tc, xf, gate=gate)


register_backend("ref", eval_int=_eval_ref,
                 doc="pure jnp searchsorted + shared Horner body (default)")
register_backend("lut_value", eval_int=_eval_lut_value,
                 doc="one gather over the pre-tabulated datapath output")
register_backend("lut_index", eval_int=_eval_lut_index,
                 doc="gathered segment index + Horner datapath")
register_backend("pallas", eval_int=_eval_pallas,
                 doc="tiled int32 Pallas kernel")
register_backend("pallas_fused", apply=_apply_fused,
                 doc="fused float->PPA->float Pallas kernel")


# --------------------------------------------------------------------------
# float deployment path
# --------------------------------------------------------------------------
def _apply_f32(tc: TableConsts, x0: jax.Array, backend: str,
               gate: bool) -> jax.Array:
    """float32 in -> float32 out deployment pipeline.

    Range reduction (hardware pre/post conditioning around the NAF unit):
      symmetry "odd":     f(-x) = -f(x)       -> evaluate |x|, restore sign
      symmetry "sigmoid": f(-x) = 1 - f(x)    -> evaluate |x|, flip output
      symmetry "minus_x": f(-x) = f(x) - x    -> softplus/silu half-line
      saturation:         x >= xe             -> sat_hi const, or x itself
                          (sat_identity: softplus/silu ~ identity above xe)
      gate:               multiply by the raw input (silu/gelu: x * T(x))

    Fused backends run all of this inside their kernel; the jnp version
    below is the reference composition the fused kernel mirrors op-for-op.
    """
    be = get_backend(backend)
    if be.apply is not None:
        return be.apply(tc, x0, gate)

    xf = jnp.abs(x0) if tc.symmetry else x0
    neg = x0 < 0

    # quantize to the input grid (round-half-away, matching to_fixed)
    scale_in = float(1 << tc.w_in)
    x_int = jnp.floor(jnp.abs(xf) * scale_in + 0.5).astype(jnp.int32)
    x_int = jnp.where(xf < 0, -x_int, x_int)  # xf >= 0 under symmetry anyway

    oob_hi = x_int >= tc.hi
    x_int_c = jnp.clip(x_int, tc.lo, tc.hi - 1)

    y_int = be.eval_int(tc, x_int_c)
    # dequantize by an exact power-of-two multiply: a float division may
    # lower to an approximate reciprocal inside a TPU kernel
    y = y_int.astype(jnp.float32) * (1.0 / (1 << tc.w_out))

    if tc.sat_identity:
        y = jnp.where(oob_hi, xf, y)
    elif tc.sat_hi is not None:
        y = jnp.where(oob_hi, jnp.float32(tc.sat_hi), y)
    if tc.symmetry == "odd":
        y = jnp.where(neg, -y, y)
    elif tc.symmetry == "sigmoid":
        y = jnp.where(neg, 1.0 - y, y)
    elif tc.symmetry == "minus_x":
        y = jnp.where(neg, y - xf, y)
    if gate:
        y = x0 * y
    return y


def _bounded(tc: TableConsts, x: jax.Array, backend: str,
             gate: bool) -> jax.Array:
    """``_apply_f32`` between two optimization barriers.

    A Pallas kernel is opaque to XLA, while the jnp backends fuse into
    their producer and consumer.  On the TPU a matmul or a reduction fused
    with different neighbours can be tiled differently and sum in another
    order, so the program around the activation would round differently
    per backend.  The barriers make every backend meet the rest of the
    program at the same materialized input and output."""
    x = jax.lax.optimization_barrier(x)
    y = _apply_f32(tc, x.astype(jnp.float32), backend, gate).astype(x.dtype)
    return jax.lax.optimization_barrier(y)


def ppa_apply(tc: TableConsts, x: jax.Array, *, backend: str = "ref"
              ) -> jax.Array:
    """Full deployment path: float in -> fixed-point PPA datapath -> float
    out, through the selected backend."""
    return _bounded(tc, x, backend, False)


def ppa_gate(tc: TableConsts, x: jax.Array, *, backend: str = "ref"
             ) -> jax.Array:
    """Gated deployment path ``x * T(x)`` (silu from a sigmoid table, gelu
    from a gelu_inner table).  The gating multiply runs in float32 before
    the output cast on every backend — inside the kernel on the fused one —
    so all backends stay bit-identical."""
    return _bounded(tc, x, backend, True)


def _exact(naf: str, x: jax.Array) -> jax.Array:
    """float32 exact evaluation of the NAF (for VJP + the `exact` impl)."""
    if naf in ("sigmoid", "sigmoid_wide"):
        return jax.nn.sigmoid(x)
    if naf in ("tanh", "tanh_wide"):
        return jnp.tanh(x)
    if naf == "exp2_frac":
        return jnp.exp2(x)
    if naf == "exp_neg":
        return jnp.exp(-x)
    if naf == "gelu_inner":
        return 0.5 * (1.0 + jax.lax.erf(x / np.float32(np.sqrt(2.0))))
    if naf == "softplus":
        return jax.nn.softplus(x)
    if naf == "silu":
        return jax.nn.silu(x)
    if naf == "recip":
        return 1.0 / x
    if naf == "rsqrt":
        return jax.lax.rsqrt(x)
    if naf == "log2":
        return jnp.log2(x)
    raise KeyError(naf)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 2))
def ppa_act(tc: TableConsts, x: jax.Array, backend: str = "ref") -> jax.Array:
    """PPA forward, exact-derivative backward (straight-through)."""
    return ppa_apply(tc, x, backend=backend)


def _ppa_act_fwd(tc, x, backend):
    return ppa_apply(tc, x, backend=backend), x


def _ppa_act_bwd(tc, backend, x, g):
    f = lambda v: _exact(tc.naf, v.astype(jnp.float32))
    _, vjp = jax.vjp(f, x)
    (dx,) = vjp(g.astype(jnp.float32))
    return (dx.astype(x.dtype),)


ppa_act.defvjp(_ppa_act_fwd, _ppa_act_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 2))
def ppa_gate_act(tc: TableConsts, x: jax.Array, backend: str = "ref"
                 ) -> jax.Array:
    """Gated PPA forward (x * T(x)), exact-derivative backward — the
    derivative of the *full* gated activation (silu'/gelu'), not of the
    inner table alone."""
    return ppa_gate(tc, x, backend=backend)


def _ppa_gate_act_fwd(tc, x, backend):
    return ppa_gate(tc, x, backend=backend), x


def _ppa_gate_act_bwd(tc, backend, x, g):
    f = lambda v: v * _exact(tc.naf, v.astype(jnp.float32))
    _, vjp = jax.vjp(f, x)
    (dx,) = vjp(g.astype(jnp.float32))
    return (dx.astype(x.dtype),)


ppa_gate_act.defvjp(_ppa_gate_act_fwd, _ppa_gate_act_bwd)


def ppa_softmax(tc_exp2: TableConsts, x: jax.Array, *, axis: int = -1,
                where: Optional[jax.Array] = None,
                backend: str = "ref") -> jax.Array:
    """Softmax with exp computed through the exp2_frac PPA table.

    exp(x - m) = 2**((x-m)*log2e) = 2**k * T(f),  k = floor(s) in [-K, 0],
    f = s - k in [0, 1).  The 2**k scale is an exact float ldexp; only the
    fractional power goes through the fixed-point datapath, exactly the
    decomposition a hardware softmax unit (MBS/TEA-S lineage) uses.
    """
    assert tc_exp2.naf == "exp2_frac", tc_exp2.naf
    xf = x.astype(jnp.float32)
    if where is not None:
        xf = jnp.where(where, xf, -jnp.inf)
    m = jnp.max(xf, axis=axis, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)  # all-masked rows
    s = (xf - m) * np.float32(math.log2(math.e))
    s = jnp.maximum(s, -24.0)               # 2^-24 underflows the table anyway
    k = jnp.floor(s)
    f = s - k                               # in [0, 1)
    pow2f = ppa_act(tc_exp2, f, backend)    # table(f) in [1, 2)
    e = pow2f * jnp.exp2(k)                 # exact scale
    if where is not None:
        e = jnp.where(where, e, 0.0)
    denom = jnp.sum(e, axis=axis, keepdims=True)
    return (e / jnp.maximum(denom, 1e-30)).astype(x.dtype)


def make_ppa_fn(table: PPATable, backend: str = "ref"):
    """Close over a packed table -> elementwise activation callable."""
    tc = pack_table(table)
    return lambda x: ppa_act(tc, x, backend)
