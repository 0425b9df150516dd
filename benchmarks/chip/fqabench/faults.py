"""Faults a serving cell can have, planted in a live engine: the check
must read ``correct`` false under each (``tests/test_bench_faults.py``
on the CPU, ``tools/control.py --faults`` at a cell's size)."""


def stale_cache(eng) -> None:
    """The decode step hands back the cache it was given: no token's K/V
    is kept past the step that made it."""
    decode = eng._decode

    def unchanged(p, c, t, pos):
        return decode(p, c, t, pos)[0], c
    eng._decode = unchanged


def altered_tokens(eng) -> None:
    """Every sampled token is replaced by its neighbour in the vocab."""
    sample = eng._sample_rows

    def altered(logits, temps, keys):
        return (sample(logits, temps, keys) + 1) % eng.cfg.vocab
    eng._sample_rows = altered
