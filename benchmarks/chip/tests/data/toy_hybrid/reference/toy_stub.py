"""A stand-in reference for the toy hybrid: it checks nothing and reads
every served token as exact.  The test that uses it is about how a
configuration joins the benchmark, not about the hybrid's numbers."""

import numpy as np


def compare(conf, seed, seqs, rows, length, control=False):
    n = sum(len(served) for _, served, _ in seqs)
    return {"err": np.zeros(n, np.float32), "gap": np.zeros(n, np.float32)}
