"""PPA kernel-layer throughput on this host: jnp ref path vs the Pallas
kernel (interpreted where the platform is the CPU) vs numpy golden, plus the model-level activation ops and the
fused float->PPA->float pipeline vs its unfused composition (Table-1
sigmoid config).  Absolute numbers are CPU-bound; the deliverable is the
relative cost and the bit-exactness cross-check at size.

``--smoke`` runs a tiny dry-run shape with minimal repeats — wired into
``scripts/ci.sh bench-smoke`` so a kernel-layer regression fails CI rather
than only the offline benchmark.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compiler import compile_or_load
from repro.core import FWLConfig, PPAScheme, eval_table_int
from repro.kernels import (pack_table, ppa_apply, ppa_eval_2d, ppa_eval_ref,
                           ppa_eval_table, ppa_gate, ppa_softmax)
from benchmarks.common import emit, timeit

# the paper's Table-1 16-bit sigmoid deployment point (FQA-O2)
TABLE1_CFG = FWLConfig(8, 16, (8, 16), (16, 16), 16)
TABLE1_SCHEME = PPAScheme(order=2, quantizer="fqa")


def main(smoke: bool = False) -> None:
    shape = (16, 128) if smoke else (256, 1024)
    reps = 1 if smoke else 10
    reps_slow = 1 if smoke else 3

    tab = compile_or_load("sigmoid", TABLE1_CFG, TABLE1_SCHEME)
    tc = pack_table(tab)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 256, shape), jnp.int32)

    ref = jax.jit(lambda v: ppa_eval_ref(v, tc.starts, tc.coefs, tc.plan))
    us = timeit(lambda: ref(x).block_until_ready(), repeats=reps)
    n = x.size
    emit("kernel/ref_jit", us, melems_per_s=f"{n / us:.1f}")

    pal = jax.jit(lambda v: ppa_eval_2d(v, tc.starts, tc.coefs, tc.plan,
                                        block=(8, 128)))
    us_p = timeit(lambda: pal(x).block_until_ready(), repeats=reps_slow)
    emit("kernel/pallas", us_p, melems_per_s=f"{n / us_p:.1f}",
         platform=jax.default_backend())

    y_ref = np.asarray(ref(x))
    y_pal = np.asarray(pal(x))
    y_tab = np.asarray(ppa_eval_table(tab, x))   # artifact->kernel adapter
    y_gold = eval_table_int(tab, np.asarray(x, np.int64))
    emit("kernel/bit_exact", 0.0,
         ref_eq_gold=bool((y_ref == y_gold).all()),
         pallas_eq_gold=bool((y_pal == y_gold).all()),
         table_adapter_eq_gold=bool((y_tab == y_gold).all()))

    # ---- model-level float act: fused vs unfused deployment path ----------
    xf = jnp.asarray(rng.normal(0, 2, shape), jnp.float32)
    act = jax.jit(lambda v: ppa_apply(tc, v))
    us_a = timeit(lambda: act(xf).block_until_ready(), repeats=reps)
    emit("kernel/ppa_apply_unfused", us_a, melems_per_s=f"{n / us_a:.1f}",
         note="jnp quantize/dequantize around the ref datapath")

    fused = jax.jit(
        lambda v: ppa_apply(tc, v, backend="pallas_fused"))
    us_f = timeit(lambda: fused(xf).block_until_ready(), repeats=reps_slow)
    emit("kernel/ppa_apply_fused", us_f, melems_per_s=f"{n / us_f:.1f}",
         vs_unfused=f"{us_a / us_f:.2f}x",
         note="one pallas_call: quantize->PPA->dequantize")

    gate_u = jax.jit(lambda v: ppa_gate(tc, v))
    us_gu = timeit(lambda: gate_u(xf).block_until_ready(), repeats=reps)
    gate_f = jax.jit(
        lambda v: ppa_gate(tc, v, backend="pallas_fused"))
    us_gf = timeit(lambda: gate_f(xf).block_until_ready(), repeats=reps_slow)
    emit("kernel/ppa_gate_fused", us_gf, unfused_us=f"{us_gu:.2f}",
         vs_unfused=f"{us_gu / us_gf:.2f}x",
         note="silu-style x*T(x) gating inside the kernel")
    emit("kernel/fused_bit_exact", 0.0,
         apply_eq=bool((np.asarray(act(xf)) == np.asarray(fused(xf))).all()),
         gate_eq=bool((np.asarray(gate_u(xf))
                       == np.asarray(gate_f(xf))).all()))

    e2 = pack_table(compile_or_load("exp2_frac", TABLE1_CFG, TABLE1_SCHEME))
    sm = jax.jit(lambda v: ppa_softmax(e2, v))
    us_s = timeit(lambda: sm(xf).block_until_ready(), repeats=reps)
    sm_exact = jax.jit(lambda v: jax.nn.softmax(v, axis=-1))
    us_e = timeit(lambda: sm_exact(xf).block_until_ready(), repeats=reps)
    emit("kernel/ppa_softmax", us_s, vs_exact=f"{us_s / us_e:.2f}x")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny dry-run shape, minimal repeats (CI gate)")
    main(smoke=ap.parse_args().smoke)
