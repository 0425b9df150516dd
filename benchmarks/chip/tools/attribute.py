"""Attribute one traced window of a cell to the program's own spans and
scopes, and read what tracing costs.

    python benchmarks/chip/tools/attribute.py <workload> <seed> <seconds> \
        [<directory to keep the gzipped trace in>]

Sets the cell up as a run does, serves one window whose last
``harness.TRACE_SECONDS`` are traced, and prints one JSON object:

- ``harness``: busy, window, top ops and idle gaps as a ``--trace 1`` run
  reports them (``tracing.reduce_trace``);
- ``program``: the same trace read by ``fqabench/program_trace.py``: idle
  by the innermost ``serve.*`` span (``idle_gaps_program``), the idle
  splits, the device time under ``act.*`` scopes, one decode program's
  device time, prompt against padded prefill tokens, and each reading of
  ``program_trace.METRICS`` under the cell's suffix;
- ``cost``: the p99 gap between tokens and the mean decode-only
  ``step()`` before the trace began and inside it, and the ``serve.*``
  spans written per ``step()``.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def _cost(w, pt) -> dict:
    """Untraced part of the window against the traced part."""
    from fqabench import tracing
    cut = w.traced_from
    gaps = {"untraced": [], "traced": []}
    for st in w.stamps.values():
        for a, b in zip(st, st[1:]):
            part = "traced" if a - w.t0 >= cut else "untraced"
            gaps[part].append(b - a)
    steps = {"untraced": [], "traced": []}
    for s in w.steps:
        if s.queue_before == 0 and not s.prefill_lens and s.decode_keys:
            steps["traced" if s.t0 >= cut else "untraced"].append(s.t1 - s.t0)
    lo, hi = next((a, b) for n, a, b in pt.trace.host_spans
                  if n == tracing.WINDOW_SPAN)
    n_steps = sum(n == "engine.step" and lo <= a < hi
                  for n, a, _ in pt.trace.host_spans)
    n_spans = sum(lo <= s.t0 < hi for s in pt.serve_spans)
    out = {"traced_from_s": cut, "steps_traced": n_steps,
           "serve_spans_per_step": n_spans / n_steps if n_steps else None}
    for part in ("untraced", "traced"):
        g, d = gaps[part], steps[part]
        out[f"itl_p99_ms.{part}"] = (1e3 * float(np.percentile(g, 99))
                                     if g else None)
        out[f"decode_step_ms.{part}"] = 1e3 * sum(d) / len(d) if d else None
        out[f"decode_steps.{part}"] = len(d)
    return out


def attribute(workload: str, seed: int, seconds: float,
              keep: str = None) -> dict:
    from fqabench import harness, program_trace, tracing

    cell = harness.Cell(workload, seed)
    suffix = workload.rsplit(".", 1)[1].split("_")[-1]
    trace_dir = tempfile.mkdtemp(prefix="fqabench-attr-")
    try:
        w, t_end, compiles = cell.measure(seed, seconds, trace_dir)
        pt = program_trace.read_program(trace_dir)
        if keep:
            os.makedirs(keep, exist_ok=True)
            path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                              recursive=True)
            with open(path, "rb") as f, gzip.open(os.path.join(
                    keep, f"{workload}.{seed}.xplane.pb.gz"), "wb") as g:
                shutil.copyfileobj(f, g)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    base = tracing.reduce_trace(pt.trace)
    s = program_trace.reduce_program(pt)
    e2e = harness.e2e_values(w, t_end)
    return {
        "workload": workload, "seed": seed, "compiles_in_window": compiles,
        "e2e": e2e,
        "harness": {"busy_s": base.busy_s, "window_s": base.window_s,
                    "idle_share": base.idle_share,
                    "device_ops": base.device_ops,
                    "idle_gaps": base.idle_gaps},
        "program": {
            "busy_s": s.busy_s, "window_s": s.window_s,
            "idle_gaps_program": s.idle_gaps_program(),
            "idle_admit_s": s.idle_admit_s, "idle_decode_s": s.idle_decode_s,
            "act_busy_s": s.act_busy_s,
            "prefill_real_tokens": s.prefill_real_tokens,
            "prefill_padded_tokens": s.prefill_padded_tokens,
            "metrics": {f"{name}.{suffix}": fn(s)
                        for name, fn in program_trace.METRICS.items()}},
        "cost": _cost(w, pt)}


def main(argv):
    out = attribute(argv[0], int(argv[1]), float(argv[2]),
                    argv[3] if len(argv) > 3 else None)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
