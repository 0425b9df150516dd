"""Time the fused PPA kernel under both segment selects, on the chip.

    PYTHONPATH=src python scripts/select_crossover.py [--out FILE]

For each table size S it compiles ``ppa_fused_2d`` once with the
comparator sweep and once with the blocked bisection (the select is a
trace-time choice of ``kernels.body.BISECT_MIN_SEGMENTS``, forced here per
compile) and reports the median device time of one call, in ms, with the
host clock around ``block_until_ready``.  The deployment tables are timed
at the served shapes: the 461-segment ``sigmoid_wide`` silu gate and the
14-segment ``exp2_frac`` softmax.  Synthetic tables of S segments (the
``sigmoid_wide`` datapath with S evenly spaced starts) locate the
crossover.  Each pair of readings also says whether the two selects gave
bit-identical outputs on the chip.  Each line of stdout is one JSON
object; the last one holds every reading.  Exits non-zero without a TPU.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compiler import compile_or_load
from repro.kernels import body, pack_table, ppa_fused_2d
from repro.kernels.fused import fused_kernel_statics
from repro.models.activations import ppa_table_jobs

#: (table, gate, rows of 128): the gates of mistral-nemo-12b's 2048-token
#: prefill and internlm2-1.8b's 1024-token prefill and 12-slot decode, and
#: the softmax scores of the first
SERVED = [("sigmoid_wide", True, 229376), ("sigmoid_wide", True, 65536),
          ("sigmoid_wide", True, 768), ("exp2_frac", False, 65536)]
SYNTHETIC_S = [8, 14, 24, 32, 48, 64, 96, 128, 256, 461]
SYNTHETIC_ROWS = 65536


def _time_ms(fn, x, reps: int = 20, rounds: int = 5) -> float:
    fn(x).block_until_ready()
    fn(x).block_until_ready()
    per_call = []
    for _ in range(rounds):
        t = time.perf_counter()
        for _ in range(reps):
            y = fn(x)
        y.block_until_ready()
        per_call.append((time.perf_counter() - t) / reps * 1e3)
    return statistics.median(per_call)


def _measure(starts, coefs, statics, gate, rows, select):
    body.BISECT_MIN_SEGMENTS = 1 if select == "bisect" else 1 << 30
    fn = jax.jit(lambda x: ppa_fused_2d(x, starts, coefs, gate=gate,
                                        **statics))
    x = jax.random.normal(jax.random.key(0), (rows, 128), jnp.float32) * 4
    t = time.perf_counter()
    fn.lower(x).compile()
    compile_s = time.perf_counter() - t
    return _time_ms(fn, x), compile_s, np.asarray(fn(x))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=None,
                    help="also write every reading to this JSON file")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("no TPU: the selects are timed on the chip only",
              file=sys.stderr)
        return 1
    default = body.BISECT_MIN_SEGMENTS
    jobs = {n: (cfg, scheme) for n, cfg, scheme in ppa_table_jobs("ppa")}
    tables = {n: pack_table(compile_or_load(n, *jobs[n]))
              for n in ("sigmoid_wide", "exp2_frac")}
    rows_out = []

    def record(**kw):
        rows_out.append(kw)
        print(json.dumps(kw), flush=True)

    def both(table, segments, starts, coefs, statics, gate, rows):
        out = {}
        for select in ("sweep", "bisect"):
            ms, cs, out[select] = _measure(starts, coefs, statics, gate,
                                           rows, select)
            record(table=table, segments=segments, rows=rows, select=select,
                   ms=round(ms, 4), compile_s=round(cs, 2),
                   identical=bool(np.array_equal(out[select],
                                                 out["sweep"])))

    for naf, gate, rows in SERVED:
        tc = tables[naf]
        both(naf, tc.num_segments, tc.starts, tc.coefs,
             fused_kernel_statics(tc), gate, rows)

    base = tables["sigmoid_wide"]
    for s in SYNTHETIC_S:
        starts = jnp.asarray(np.linspace(base.lo, base.hi, s, endpoint=False,
                                         dtype=np.int64), jnp.int32)
        coefs = jnp.resize(base.coefs, (s, base.coefs.shape[1]))
        statics = dict(fused_kernel_statics(base), num_segments=s)
        both("synthetic", s, starts, coefs, statics, True, SYNTHETIC_ROWS)
    body.BISECT_MIN_SEGMENTS = default

    summary = {"device": jax.devices()[0].device_kind, "readings": rows_out}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
