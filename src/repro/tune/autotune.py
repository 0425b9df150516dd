"""The autotuner: measure candidate execution configs, persist the winner.

Three staged sweeps, each timing the *real* code paths (the same compile
and kernel calls the benchmark harness drives — ``compile_table`` with a
fresh session per measurement so nothing is answered from a warm cache,
and ``ppa_fused_apply`` on a packed table):

  1. (search backend × speculation depth) over a small compile grid;
  2. jax padding floors (``K_FLOOR``/``G_FLOOR``/``BATCH_ELEMS``), only
     when the jax backend won stage 1;
  3. ``pallas_fused`` block shape on a representative tensor.

The winner is persisted device-keyed next to the ``TableStore``
(:func:`repro.tune.config.save_tuned`) where ``compile_or_load``, sweeps
and ``ServeEngine`` auto-resolve it.  Every candidate is an execution
knob: the compiled tables used for timing are also compared by
``table_identity`` across candidates, so a tuning run doubles as a
bit-identity smoke test.

CLI (used by ``scripts/ci.sh tune-smoke`` and ``scripts/sweep.py
--retune``)::

    python -m repro.tune.autotune --store DIR [--smoke] [--verify]
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from repro.compiler.compile import (CompilerSession, compile_table,
                                    table_identity)
from repro.core.datapath import FWLConfig
from repro.core.schemes import PPAScheme
from repro.core.searchspace import jax_backend_available

from .config import TunedConfig, device_key, save_tuned

__all__ = ["autotune", "main"]

#: compile grid the candidates are timed on.  Smoke: two order-1 7-bit
#: NAFs (seconds).  Full: adds an order-2 point so floor tuning sees the
#: dispatch shapes that dominate real sweeps.
_CFG1 = FWLConfig(7, 7, (7,), (7,), 7)
_CFG2 = FWLConfig(7, 7, (7, 7), (7, 7), 7)
_SMOKE_GRID = [("sigmoid", _CFG1), ("tanh", _CFG1)]
_FULL_GRID = _SMOKE_GRID + [("gelu_inner", _CFG1), ("sigmoid", _CFG2)]

_SCHEME = PPAScheme(1, None, "fqa")


def _time_compile_grid(grid, *, backend, speculate, repeats: int) -> Tuple[float, List[dict]]:
    """Median wall seconds to compile the grid cold (fresh session each
    repeat — the autotuner times compiles, not cache hits)."""
    times = []
    tables = None
    for _ in range(repeats):
        session = CompilerSession()
        t0 = time.perf_counter()
        tabs = [compile_table(naf, cfg, _SCHEME, session=session,
                              search_backend=backend, speculate=speculate)
                for naf, cfg in grid]
        times.append(time.perf_counter() - t0)
        tables = tabs
    times.sort()
    return times[len(times) // 2], [table_identity(t) for t in tables]


def _time_fused_block(table, block: Tuple[int, int],
                      repeats: int) -> float:
    """Median wall seconds for one jitted fused activation pass at
    ``block``."""
    import jax.numpy as jnp

    from repro.kernels.fused import ppa_fused_apply
    from repro.kernels.ops import pack_table

    tc = pack_table(table)
    x = jnp.linspace(-0.9, 0.9, 64 * 1024, dtype=jnp.float32)
    fn = jax.jit(lambda v: ppa_fused_apply(tc, v, block=block))
    fn(x).block_until_ready()                    # compile outside the clock
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def autotune(root: "str | Path | None" = None, *, smoke: bool = False,
             repeats: Optional[int] = None,
             log=print) -> TunedConfig:
    """Measure the candidate configs and return (and persist) the winner.

    ``root=None`` measures without persisting.  ``smoke`` shrinks every
    stage to a seconds-scale run (the CI shape); the knobs it skips keep
    their defaults.
    """
    repeats = repeats if repeats is not None else (1 if smoke else 3)
    grid = _SMOKE_GRID if smoke else _FULL_GRID
    score: Dict[str, float] = {}

    backends = ["numpy"]
    jax_ok, jax_why = jax_backend_available()
    if jax_ok:
        backends.append("jax")
    else:
        log(f"[tune] jax search backend unavailable ({jax_why}); "
            f"tuning numpy only")
    speculates = [0, 3]

    # stage 1 — search backend × speculation depth
    best: Tuple[float, str, int] = (float("inf"), "numpy", 0)
    identity = None
    for backend in backends:
        for spec in speculates:
            wall, ident = _time_compile_grid(grid, backend=backend,
                                             speculate=spec,
                                             repeats=repeats)
            score[f"compile_s/{backend}/spec{spec}"] = round(wall, 4)
            log(f"[tune] backend={backend} speculate={spec}: {wall:.3f}s")
            if identity is None:
                identity = ident
            elif ident != identity:
                raise AssertionError(
                    f"tuning candidate backend={backend} speculate={spec} "
                    f"changed the compiled tables — execution knobs must "
                    f"be bit-neutral")
            if wall < best[0]:
                best = (wall, backend, spec)
    _, backend, speculate = best
    score[f"compile_s/{backend}/spec{speculate}"] = round(best[0], 4)
    score["winner/backend_spec"] = best[0]

    # stage 2 — jax padding floors (only meaningful when jax won)
    k_floor, g_floor, batch_elems = 64, 32, 1 << 23
    if backend == "jax":
        from repro.core.searchspace import JaxSearchBackend
        floor_grid: Sequence[Tuple[int, int, int]] = (
            [(32, 32, 1 << 23), (64, 32, 1 << 23)] if smoke else
            [(32, 16, 1 << 23), (32, 32, 1 << 23), (64, 32, 1 << 23),
             (64, 32, 1 << 21), (128, 32, 1 << 23), (64, 64, 1 << 23)])
        floor_best = (float("inf"), (k_floor, g_floor, batch_elems))
        for kf, gf, be in floor_grid:
            inst = JaxSearchBackend(k_floor=kf, g_floor=gf, batch_elems=be)
            wall, ident = _time_compile_grid(grid, backend=inst,
                                             speculate=speculate,
                                             repeats=repeats)
            score[f"compile_s/jax/K{kf}-G{gf}-B{be}"] = round(wall, 4)
            log(f"[tune] floors K{kf}/G{gf}/B{be}: {wall:.3f}s")
            if ident != identity:
                raise AssertionError(
                    f"floor candidate K{kf}/G{gf}/B{be} changed the "
                    f"compiled tables — padding must be bit-neutral")
            if wall < floor_best[0]:
                floor_best = (wall, (kf, gf, be))
        k_floor, g_floor, batch_elems = floor_best[1]

    # stage 3 — fused kernel block shape, timed on the compiled kernel.
    # Where the platform is the CPU the kernel only interprets, and an
    # interpreter's timings say nothing about the device: the default
    # block stands there.
    block = (256, 128)
    if jax.default_backend() == "cpu":
        log("[tune] fused block sweep needs an accelerator; "
            f"keeping {block[0]}x{block[1]}")
    else:
        naf, cfg = grid[0]
        table = compile_table(naf, cfg, _SCHEME, search_backend="numpy")
        blocks: Sequence[Tuple[int, int]] = (
            [(128, 128), (256, 128)] if smoke else
            [(128, 128), (256, 128), (512, 128), (256, 256)])
        block_best = (float("inf"), block)
        for b in blocks:
            wall = _time_fused_block(table, b, repeats=max(repeats, 2))
            score[f"fused_s/{b[0]}x{b[1]}"] = round(wall, 4)
            log(f"[tune] fused block {b[0]}x{b[1]}: {wall*1e3:.1f}ms")
            if wall < block_best[0]:
                block_best = (wall, b)
        block = block_best[1]

    cfg = TunedConfig(device=device_key(), search_backend=backend,
                      speculate=speculate, k_floor=k_floor, g_floor=g_floor,
                      batch_elems=batch_elems, block=block, score=score)
    log(f"[tune] winner: {cfg.summary()}")
    if root is not None:
        path = save_tuned(cfg, root)
        log(f"[tune] persisted {path}")
    return cfg


def _verify(root: Path, cfg: TunedConfig) -> None:
    """Round-trip + pickup assertions (the tune-smoke CI contract)."""
    from repro.compiler.store import TableStore

    from .config import load_tuned, resolve_tuned

    reloaded = load_tuned(root)
    assert reloaded == cfg, (
        f"persisted config did not round-trip:\n{reloaded}\n!=\n{cfg}")
    assert resolve_tuned(root) == cfg

    store = TableStore(root)
    naf, fcfg = _SMOKE_GRID[0]
    tuned_tab = store.compile_or_load(naf, fcfg, _SCHEME)
    assert store.tuned_applied >= 1, (
        "compile_or_load did not pick up the persisted tuned config")
    # tuned execution must not move the artifact: byte-compare against an
    # untuned compile of the same job
    untuned = compile_table(naf, fcfg, _SCHEME, search_backend="numpy",
                            speculate=0)
    assert table_identity(tuned_tab) == table_identity(untuned), (
        "tuned compile produced a different artifact")
    print(f"[tune] verify OK: round-trip + compile_or_load pickup "
          f"(tuned_applied={store.tuned_applied})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--store", type=Path, default=None,
                    help="store root to persist the config next to "
                         "(default: measure only, do not persist)")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI shape")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--verify", action="store_true",
                    help="after tuning, assert the persisted config "
                         "round-trips and is picked up by compile_or_load "
                         "(requires --store)")
    args = ap.parse_args(argv)
    if args.verify and args.store is None:
        ap.error("--verify requires --store")
    cfg = autotune(args.store, smoke=args.smoke, repeats=args.repeats)
    if args.verify:
        _verify(args.store, cfg)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
