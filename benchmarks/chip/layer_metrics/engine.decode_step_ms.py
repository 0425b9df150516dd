"""Mean host wall time of the window's ``step()`` calls that found the
queue empty and so only decoded (every step ends in a host sync)."""


def read(run):
    ts = [s.t1 - s.t0 for s in run.steps
          if s.queue_before == 0 and not s.prefill_lens and s.decode_keys]
    return 1e3 * sum(ts) / len(ts) if ts else None
