"""Pluggable execution backends for the full-space candidate scan.

The paper's entire software cost is the candidate scan of Alg. 1/2: for
every probed window, thousands of candidate coefficient sets are pushed
through the fixed-point Horner datapath, the intercept is error-flattened
per candidate, and the MAE is reduced over the grid.  This module owns that
block evaluation — extracted from ``Quantizer.fit_segment`` — behind a
small backend contract so the *same* scan can execute eagerly on numpy
(the golden reference) or as a jitted, candidate-axis-batched XLA program:

  * :class:`NumpySearchBackend` — the golden model.  Bit-identical to the
    seed ``eval_block`` (same ops through :func:`~.datapath.horner_body`).
  * :class:`JaxSearchBackend` — the same code path traced under jnp with
    x64 enabled (int64/float64, scoped via ``jax.enable_x64(True)``
    so the rest of the process keeps jax's default dtypes).  The window
    grid is staged device-resident once per segment context; candidate
    blocks and grids are padded to power-of-two buckets (edge replication,
    which leaves every reduction unchanged) so the number of retraces is
    bounded by the bucket count, not the window count.  A vmapped variant
    evaluates many windows in ONE dispatch — the primitive TBW speculative
    probe batching builds on.

Bit-identity is a hard contract, not an aspiration: every op in the shared
code path (:func:`_block_metrics`) is either exact integer arithmetic or an
IEEE-754 elementwise/min-max operation with no rounding freedom, so numpy
and XLA produce the same bits (tests/test_searchspace.py asserts it across
quantizers, modes and the NAF zoo).

Backend selection never changes results, so it is deliberately kept out of
every content address (``CompileJob.key``): ``make_quantizer(...,
backend=...)``, ``compile_table(..., search_backend=...)`` and the
``REPRO_SEARCH_BACKEND`` environment variable (the per-host operator knob
for live sweeps) all plumb into :func:`resolve_backend`.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .datapath import DatapathPlan, FWLConfig, apply_shift, horner_body
from .fixed_point import round_half_away

__all__ = [
    "SegmentContext",
    "SearchBackend",
    "NumpySearchBackend",
    "JaxSearchBackend",
    "SEARCH_BACKENDS",
    "resolve_backend",
    "jax_backend_available",
]

#: env var consulted by :func:`resolve_backend` when no explicit backend is
#: given — the per-host override for sweeps (docs/OPERATIONS.md).
BACKEND_ENV = "REPRO_SEARCH_BACKEND"


@dataclasses.dataclass
class SegmentContext:
    """Per-segment scan state shared by every block evaluation.

    Created once per ``fit_segment`` call; backends stash device-resident
    copies of the grid under ``cache`` so repeated chunk dispatches against
    the same window pay the host->device transfer once.
    """

    x_int: np.ndarray           # (G,) grid integers, FWL cfg.w_in
    f_vals: np.ndarray          # (G,) float64 target values
    f_q: np.ndarray             # (G,) target rounded to the w_out grid
    cfg: FWLConfig
    plan: DatapathPlan
    flatten_b: bool             # error-flatten the intercept per candidate
    b_fixed: int = 0            # pre-rounded intercept when flatten_b=False
    cache: dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def num(self) -> int:
        return int(self.x_int.size)


def _block_metrics(plan: DatapathPlan, w_b: int, flatten_b: bool,
                   planes: Sequence, b_fixed, x, f, f_q, xp,
                   argmin_mae0: bool = False):
    """The one candidate-block evaluation, array-namespace agnostic.

    Args:
      planes: ``plan.order`` candidate coefficient arrays, shape (K,).
      b_fixed: scalar intercept integer (read only when not flatten_b).
      x/f/f_q: the window grid, shape (G,).
      xp: numpy or jax.numpy — only ``* + >> << abs where floor ceil
        zeros_like full_like`` and axis reductions are used, so the same
        function body is the numpy golden model and the XLA trace.
      argmin_mae0: compute MAE_0 with a single (G,) pass at the
        first-argmin row of ``mae`` instead of a full (K, G) pass —
        exploiting the contract below.  The eager numpy backend uses it
        (the seed model never paid a full MAE_0 pass); under XLA the full
        reduction fuses into the block for free.

    Returns (mae (K,), b_int (K,), mae0 (K,)) — the per-candidate MAE_hard,
    flattened-and-rounded intercept, and MAE_0 (paper Eq. 7).  Contract:
    ``mae0`` is only guaranteed valid at the FIRST argmin row of ``mae``
    (ties broken low, as ``argmin`` does) — the one row the scan ever
    reads (``_SegmentScan.consume``; the warm block is K=1).
    """
    sel = [p[:, None] for p in planes]
    sel.append(xp.zeros_like(planes[0])[:, None])       # b=0: pre-intercept
    _, (hp, w_pre) = horner_body(plan, sel, x, return_pre_b=True)
    f64 = f.dtype
    if flatten_b:
        # error-flatten the intercept per candidate (Alg. 1 lines 7-9)
        e0 = f[None, :] - hp.astype(f64) / (1 << w_pre)
        b = 0.5 * (e0.max(axis=-1) + e0.min(axis=-1))
        v = b * (1 << w_b)
        b_int = xp.where(v >= 0, xp.floor(v + 0.5),
                         xp.ceil(v - 0.5)).astype(hp.dtype)
    else:
        b_int = xp.full_like(planes[0], b_fixed)
    # concat add at w_sum = max(w_pre, w_b), then rescale to w_out
    w_sum = max(w_pre, w_b)
    out = apply_shift(hp, w_pre - w_sum) \
        + apply_shift(b_int[:, None], w_b - w_sum)
    out = apply_shift(out, w_sum - plan.w_out)
    y = out.astype(f64) / (1 << plan.w_out)
    mae = xp.abs(f[None, :] - y).max(axis=-1)
    if argmin_mae0:
        mae0 = xp.broadcast_to(xp.abs(f_q - y[xp.argmin(mae)]).max(),
                               mae.shape)
    else:
        mae0 = xp.abs(f_q[None, :] - y).max(axis=-1)
    return mae, b_int, mae0


BlockResult = Tuple[np.ndarray, np.ndarray, np.ndarray]   # (mae, b_int, mae0)


class SearchBackend:
    """Executes candidate blocks; never decides anything.

    The scan loop (chunk order, warm starts, early exit, store caps) lives
    in ``Quantizer``/``_SegmentScan`` and is shared verbatim by every
    backend, so a backend cannot change which candidate wins — only how
    fast the blocks evaluate.  Contract: ``eval_block`` returns float64 /
    int64 numpy arrays bit-identical to the numpy golden backend.
    """

    name = "base"

    def context(self, x_int: np.ndarray, f_vals: np.ndarray, cfg: FWLConfig,
                *, flatten_b: bool, b_fixed: int = 0) -> SegmentContext:
        f_vals = np.asarray(f_vals, dtype=np.float64)
        f_q = round_half_away(f_vals * (1 << cfg.w_out)).astype(np.float64) \
            / (1 << cfg.w_out)
        return SegmentContext(
            x_int=np.asarray(x_int, dtype=np.int64), f_vals=f_vals, f_q=f_q,
            cfg=cfg, plan=DatapathPlan.from_config(cfg),
            flatten_b=flatten_b, b_fixed=int(b_fixed))

    def eval_block(self, ctx: SegmentContext,
                   a_list: Sequence[np.ndarray]) -> BlockResult:
        raise NotImplementedError

    def eval_block_multi(self, blocks: Sequence[Tuple[SegmentContext,
                                                      Sequence[np.ndarray]]]
                         ) -> List[BlockResult]:
        """Evaluate blocks of several windows; backends that can fuse them
        into one dispatch override this.  Semantics are exactly a loop."""
        return [self.eval_block(ctx, a_list) for ctx, a_list in blocks]

    def eval_block_batch(self, ctx: SegmentContext,
                         blocks: Sequence[Sequence[np.ndarray]]):
        """Evaluate a sequence of blocks of ONE window; results come back
        in block order, as an iterable.

        The base implementation is LAZY (a generator): a feasible-mode
        caller that early-exits simply stops consuming, and the remaining
        blocks are never computed — so eager backends' semantics and the
        golden model's compute stay exactly the seed's.  Device backends
        override this to fuse blocks into grouped dispatches (speculative
        lookahead: results past an early exit are computed and discarded,
        trading wasted lanes for dispatch count).
        """
        return (self.eval_block(ctx, blk) for blk in blocks)


class NumpySearchBackend(SearchBackend):
    """Eager numpy golden model (the seed ``eval_block``, verbatim ops)."""

    name = "numpy"

    def eval_block(self, ctx, a_list):
        planes = [np.asarray(a, dtype=np.int64) for a in a_list]
        return _block_metrics(ctx.plan, ctx.cfg.w_b, ctx.flatten_b, planes,
                              ctx.b_fixed, ctx.x_int, ctx.f_vals, ctx.f_q,
                              np, argmin_mae0=True)


# --------------------------------------------------------------------- jax
_JAX_STATE: Optional[Tuple[bool, str]] = None


def jax_backend_available() -> Tuple[bool, str]:
    """(ok, reason) — whether the jitted x64 backend can run here."""
    global _JAX_STATE
    if _JAX_STATE is None:
        # jax is a hard dependency: an import or API error here is a broken
        # install and propagates; only a device without int64 says "no".
        import jax
        import jax.numpy as jnp
        with jax.enable_x64(True):
            probe = jnp.asarray(np.arange(2, dtype=np.int64))
        if str(probe.dtype) != "int64":
            _JAX_STATE = (False, f"x64 scope yielded {probe.dtype}, "
                                 "not int64")
        else:
            _JAX_STATE = (True, f"jax {jax.__version__}")
    return _JAX_STATE


def _bucket(n: int, lo: int) -> int:
    """Smallest power-of-two >= n, floored at ``lo`` — the padded-shape
    policy that bounds jit retraces to O(log(max size)) per plan."""
    b = lo
    while b < n:
        b <<= 1
    return b


def _pad_edge(a: np.ndarray, n: int) -> np.ndarray:
    """Pad a 1-D array to length ``n`` by replicating its last element.

    Replication (never zeros) keeps every reduction in ``_block_metrics``
    exact: a duplicated grid point cannot move a max/min, and duplicated
    candidates are sliced off the result before anyone looks at them.
    """
    return a if a.size == n else np.pad(a, (0, n - a.size), mode="edge")


@functools.lru_cache(maxsize=None)
def _jitted_block_fn(plan: DatapathPlan, w_b: int, flatten_b: bool,
                     multi: Optional[str]):
    """One compiled XLA program per (plan, w_b, flatten_b, multi) —
    everything else (bucketed shapes) is handled by jit's own trace cache.

    ``multi``: None = one block of one window; ``"windows"`` = vmap over a
    stacked window axis on every operand (speculative multi-window
    prefetch — each window brings its own grid); ``"blocks"`` = vmap over
    the candidate stack only, grid and intercept shared (a full scan's
    chunk sequence — the device-resident grid is staged once).
    """
    import jax
    import jax.numpy as jnp

    def fn(a_stack, b_fixed, x, f, f_q):
        planes = [a_stack[i] for i in range(plan.order)]
        return _block_metrics(plan, w_b, flatten_b, planes, b_fixed,
                              x, f, f_q, jnp)

    if multi == "windows":
        fn = jax.vmap(fn)
    elif multi == "blocks":
        fn = jax.vmap(fn, in_axes=(0, None, None, None, None))
    return jax.jit(fn)


class JaxSearchBackend(SearchBackend):
    """Jitted, device-resident candidate scan (x64, bucketed shapes).

    All device work runs under a *scoped* ``enable_x64`` so the backend can
    use int64/float64 (required: order-2 16-bit intermediates exceed int32)
    without flipping process-global jax defaults for the rest of the repo —
    the kernels and models keep their int32/float32 behaviour.
    """

    name = "jax"

    #: padding floors: blocks smaller than these are padded up — one trace
    #: serves every probe-sized dispatch (warm starts are K=1).  These
    #: class attributes are the process defaults; the autotuner
    #: (:mod:`repro.tune`) overwrites them with the persisted per-device
    #: winners, and individual instances can override via the constructor
    #: (used by the autotuner's own measurement sweeps).  Pure execution
    #: knobs: padded lanes are sliced off before anyone reads them, so
    #: results are floor-independent.
    K_FLOOR = 64
    G_FLOOR = 32

    def __init__(self, *, k_floor: Optional[int] = None,
                 g_floor: Optional[int] = None,
                 batch_elems: Optional[int] = None):
        ok, why = jax_backend_available()
        if not ok:
            raise RuntimeError(f"jax search backend unavailable ({why}); "
                               f"use backend='numpy'")
        if k_floor is not None:
            self.K_FLOOR = int(k_floor)
        if g_floor is not None:
            self.G_FLOOR = int(g_floor)
        if batch_elems is not None:
            self.BATCH_ELEMS = int(batch_elems)

    # -- device staging --------------------------------------------------------
    def _grid(self, ctx: SegmentContext, gp: int):
        """Device-resident (x, f, f_q) padded to the ``gp`` bucket, staged
        once per (context, bucket) — the 'grid device-resident per segment'
        half of the contract."""
        dev = ctx.cache.get(("jax", gp))
        if dev is None:
            import jax
            import jax.numpy as jnp
            with jax.enable_x64(True):
                dev = (jnp.asarray(_pad_edge(ctx.x_int, gp)),
                       jnp.asarray(_pad_edge(ctx.f_vals, gp)),
                       jnp.asarray(_pad_edge(ctx.f_q, gp)))
            ctx.cache[("jax", gp)] = dev
        return dev

    def eval_block(self, ctx, a_list):
        import jax
        import jax.numpy as jnp
        k = int(a_list[0].size)
        kp = _bucket(k, self.K_FLOOR)
        gp = _bucket(ctx.num, self.G_FLOOR)
        a_stack = np.stack([_pad_edge(np.asarray(a, dtype=np.int64), kp)
                            for a in a_list])
        fn = _jitted_block_fn(ctx.plan, ctx.cfg.w_b, ctx.flatten_b, None)
        with jax.enable_x64(True):
            x, f, f_q = self._grid(ctx, gp)
            mae, b_int, mae0 = fn(jnp.asarray(a_stack),
                                  jnp.asarray(np.int64(ctx.b_fixed)),
                                  x, f, f_q)
            # backend contract: eval_block returns host numpy — ONE sync
            # per dispatched block, at the API boundary, by design.
            # analysis: allow(host-sync)
            return (np.asarray(mae)[:k], np.asarray(b_int)[:k],
                    np.asarray(mae0)[:k])

    def eval_block_multi(self, blocks):
        """Many windows, ONE dispatch: vmap over a stacked window axis.

        Windows are padded to shared (K, G) buckets and the window count
        itself is bucketed (replicating window 0), so the speculative-probe
        batches TBW issues — 1..2^depth windows of probe-sized blocks —
        reuse a handful of traces.  Per-window results are sliced back out;
        padding windows are discarded unread.
        """
        if len(blocks) == 1:
            ctx, a_list = blocks[0]
            return [self.eval_block(ctx, a_list)]
        import jax
        import jax.numpy as jnp
        plan = blocks[0][0].plan
        w_b = blocks[0][0].cfg.w_b
        flatten_b = blocks[0][0].flatten_b
        for ctx, _ in blocks:
            if (ctx.plan, ctx.cfg.w_b, ctx.flatten_b) != (plan, w_b,
                                                          flatten_b):
                raise ValueError("eval_block_multi requires one shared "
                                 "datapath plan across windows")
        ks = [int(a[0].size) for _, a in blocks]
        kp = _bucket(max(ks), self.K_FLOOR)
        gp = _bucket(max(ctx.num for ctx, _ in blocks), self.G_FLOOR)
        wp = _bucket(len(blocks), 1)
        idx = list(range(len(blocks))) + [0] * (wp - len(blocks))
        a = np.stack([np.stack([_pad_edge(np.asarray(ai, dtype=np.int64), kp)
                                for ai in blocks[i][1]]) for i in idx])
        x = np.stack([_pad_edge(blocks[i][0].x_int, gp) for i in idx])
        f = np.stack([_pad_edge(blocks[i][0].f_vals, gp) for i in idx])
        f_q = np.stack([_pad_edge(blocks[i][0].f_q, gp) for i in idx])
        b_fixed = np.array([blocks[i][0].b_fixed for i in idx],
                           dtype=np.int64)
        fn = _jitted_block_fn(plan, w_b, flatten_b, "windows")
        with jax.enable_x64(True):
            mae, b_int, mae0 = fn(jnp.asarray(a), jnp.asarray(b_fixed),
                                  jnp.asarray(x), jnp.asarray(f),
                                  jnp.asarray(f_q))
            # backend contract: one sync for the WHOLE multi-window batch
            # (that amortization is this method's reason to exist).
            # analysis: allow(host-sync)
            mae, b_int, mae0 = (np.asarray(mae), np.asarray(b_int),
                                np.asarray(mae0))
        return [(mae[i][:ks[i]], b_int[i][:ks[i]], mae0[i][:ks[i]])
                for i in range(len(blocks))]

    #: element budget (window-axis x candidates x grid) for one fused
    #: full-scan dispatch — bounds the padded intermediates XLA
    #: materializes (int64: 8 bytes/element per temporary).  Order-1 full
    #: scans fuse into a single dispatch; order-2 scans split into a few.
    BATCH_ELEMS = 1 << 23

    def eval_block_batch(self, ctx, blocks):
        """Fuse a full scan's chunk sequence into grouped vmapped
        dispatches (one window, many blocks — no early exit to respect).

        All blocks share ``ctx``, so the grid rides the per-context device
        cache and the vmap batches only the candidate stacks
        (``in_axes=(0, None, ...)``) — no per-dispatch grid transfer.
        """
        if len(blocks) <= 1:
            return super().eval_block_batch(ctx, blocks)
        import jax
        import jax.numpy as jnp
        gp = _bucket(ctx.num, self.G_FLOOR)
        fn = _jitted_block_fn(ctx.plan, ctx.cfg.w_b, ctx.flatten_b,
                              "blocks")
        out: List[BlockResult] = []
        group: List[Sequence[np.ndarray]] = []
        kp_max = 0

        def flush():
            nonlocal group, kp_max
            if group:
                ks = [int(blk[0].size) for blk in group]
                wp = _bucket(len(group), 1)
                idx = list(range(len(group))) + [0] * (wp - len(group))
                a = np.stack([np.stack(
                    [_pad_edge(np.asarray(ai, dtype=np.int64), kp_max)
                     for ai in group[i]]) for i in idx])
                with jax.enable_x64(True):
                    x, f, f_q = self._grid(ctx, gp)
                    mae, b_int, mae0 = fn(
                        jnp.asarray(a), jnp.asarray(np.int64(ctx.b_fixed)),
                        x, f, f_q)
                    # backend contract: one sync per fused chunk group
                    # (bounded by BATCH_ELEMS), not per chunk.
                    # analysis: allow(host-sync)
                    mae, b_int, mae0 = (np.asarray(mae), np.asarray(b_int),
                                        np.asarray(mae0))
                out.extend((mae[i][:ks[i]], b_int[i][:ks[i]],
                            mae0[i][:ks[i]]) for i in range(len(group)))
            group, kp_max = [], 0

        for blk in blocks:
            kp = _bucket(int(blk[0].size), self.K_FLOOR)
            new_kp = max(kp_max, kp)
            if group and (len(group) + 1) * new_kp * gp > self.BATCH_ELEMS:
                flush()
                new_kp = kp
            group.append(blk)
            kp_max = new_kp
        flush()
        return out


SEARCH_BACKENDS = {
    "numpy": NumpySearchBackend,
    "jax": JaxSearchBackend,
}


def resolve_backend(spec: "str | SearchBackend | None" = None
                    ) -> SearchBackend:
    """One resolver for every plumbing path.

    ``spec`` may be a backend instance (returned as-is), a registry name,
    or None — which falls back to ``$REPRO_SEARCH_BACKEND`` and then to
    the numpy golden backend.  Selection is FWLConfig-independent and
    address-independent: the store key of a compile never encodes it.
    """
    if isinstance(spec, SearchBackend):
        return spec
    name = spec or os.environ.get(BACKEND_ENV) or "numpy"
    try:
        cls = SEARCH_BACKENDS[name]
    except KeyError as e:
        raise KeyError(f"unknown search backend {name!r} "
                       f"(available: {sorted(SEARCH_BACKENDS)})") from e
    return cls()
