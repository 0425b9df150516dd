"""Readings for the correctness limits of a cell, through the harness's
own comparison (``Cell.readings``, ``Cell.check``, ``judge``): on each
seed, what the program's served tokens read against the float32
reference (the lower readings); on the first ``n_control`` seeds, what
the int8 reference put in the program's place reads on the same prompts
and tokens (the upper readings), and whether each comes out correct
against the cell's ``limits/<workload>.json``.  With ``--faults``, one
more window on the first seed with each fault planted in the engine: a
decode step that keeps its input cache, and every token altered where it
is sampled.

    python benchmarks/chip/tools/control.py <workload> <seconds> \
        <n_control> [--faults] <seed> ...

One process: the cell is set up once and re-drawn for each seed.  Each
window runs at the cell's own load and length ``seconds``.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv):
    from fqabench import harness
    from fqabench.faults import altered_tokens, stale_cache

    workload, seconds, n_control = argv[0], float(argv[1]), int(argv[2])
    faults = "--faults" in argv[3:]
    seeds = [int(s) for s in argv[3:] if s != "--faults"]
    cell = harness.Cell(workload, seeds[0])

    def window(seed, label):
        w, _, compiles = cell.measure(seed, seconds)
        seqs = cell.sample(w, seed)
        cell.eng.run_until_drained()
        cell.eng.params = None            # room for the reference
        return seqs, {"what": label, "seed": seed, "compiles": compiles,
                      "tokens": sum(len(s[1]) for s in seqs)}

    def read(row, seed, seqs, control=False):
        t = time.perf_counter()
        key = "control" if control else "program"
        row[key] = cell.readings(seed, seqs, control)
        row[key + "_correct"] = harness.judge(cell.limited(row[key]))
        row[key + "_s"] = time.perf_counter() - t

    for i, seed in enumerate(seeds):
        if i:
            cell.reseed(seed)
        seqs, row = window(seed, "sound")
        read(row, seed, seqs)
        if i < n_control:
            read(row, seed, seqs, control=True)
        print(json.dumps(row), flush=True)
    if not faults:
        return
    for plant in (stale_cache, altered_tokens):
        cell.reseed(seeds[0])
        eng = cell.eng
        kept = eng._decode, eng._sample_rows
        plant(eng)
        seqs, row = window(seeds[0], plant.__name__)
        read(row, seeds[0], seqs)
        eng._decode, eng._sample_rows = kept
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
