"""Find the knee of an open-loop cell: serve its traffic at each of a
list of rates, one window each, in one process, and print what each
rate gives.

    python benchmarks/chip/tools/sweep.py <workload> <seconds> <rate> ...

The knee is the highest rate whose backlog does not grow over the window
(few requests left unfinished at its end, time to first token flat).
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv):
    from fqabench import harness

    workload, seconds, rates = argv[0], float(argv[1]), map(float, argv[2:])
    cell = harness.Cell(workload, 1)
    for i, rate in enumerate(rates):
        mix = dataclasses.replace(cell.mix, spec=dict(cell.mix.spec,
                                                      rate_per_s=rate))
        w, t_end, compiles = cell.measure(1000 + i, seconds, mix=mix)
        vals = harness.e2e_values(w, t_end)
        late = [r for r in w.submitted if not r.done]
        st = cell.eng.stats()
        print(json.dumps({
            "rate_per_s": rate, "due": len(w.submitted),
            "unfinished": len(late), "queued_at_end": st["queue_depth"],
            "steps": len(w.steps), "compiles": compiles, **vals}),
            flush=True)
        cell.eng.run_until_drained()


if __name__ == "__main__":
    main(sys.argv[1:])
