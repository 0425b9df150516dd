"""Mean host wall time of the window's ``step()`` calls that admitted at
least one request (a batched prefill, then the decode step)."""


def read(run):
    ts = [s.t1 - s.t0 for s in run.steps if s.prefill_lens]
    return 1e3 * sum(ts) / len(ts) if ts else None
