"""Share of the HBM roofline reached by the decode-only steps: the bytes
they need, by the program module's counts (``dense_gqa``: every weight
once at its stored dtype, one embedding row per sequence, the K/V of
each sequence's live context), over their host wall time times the
chip's HBM bandwidth.  Bound by bytes, not FLOPs."""


def read(run):
    steps = [s for s in run.steps
             if s.queue_before == 0 and not s.prefill_lens and s.decode_keys]
    if not steps:
        return None
    need = sum(run.counts.decode_step_bytes(s.decode_keys) for s in steps)
    took = sum(s.t1 - s.t0 for s in steps)
    return 100.0 * need / (took * run.peaks["hbm_bytes_per_s"])
