"""Compile a cell's largest prefill and its decode step for a described
TPU v5e (no chip needed) and print the memory each program needs.

    JAX_PLATFORMS=cpu python benchmarks/chip/tools/size_slots.py \
        <config> <traffic> [n_slots ...]

For each ``n_slots`` it compiles the prefill of a full admission group
(``n_slots`` prompts in the mix's largest bucket) and the decode step
over ``n_slots`` cache rows, with bf16 weights as arguments, and prints
``compiled.memory_analysis()``.  Weights + cache + the prefill's
temporaries must fit the chip's 16 GB.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from fqabench import harness
    from repro.models import (ShardCtx, decode_step, init_cache,
                              make_model_acts, param_specs, prefill)
    from repro.models.common import abstract_params

    jax.config.update("jax_enable_compilation_cache", False)
    conf = harness.load_config(HERE, argv[0])
    mix = harness.load_mix(HERE, argv[1])
    slots = [int(a) for a in argv[2:]] or [mix.n_slots]
    cfg = harness.load_program(HERE, conf).program_cfg(conf)
    store = harness._tables(cfg.act_impl, HERE / ".tables")
    acts = make_model_acts(cfg, store)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def on_dev(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)

    params = on_dev(abstract_params(param_specs(cfg), jnp.bfloat16))
    blen = max(b for b, _, _ in
               mix.warm_shapes(lambda n: 1 << (n - 1).bit_length()))
    gib = 1 << 30
    for n in slots:
        batch = {"tokens": jax.ShapeDtypeStruct((n, blen), jnp.int32,
                                                sharding=dev)}
        last = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=dev)
        pf = jax.jit(lambda p, b, l: prefill(p, cfg, b, mix.cache_len, acts,
                                             ShardCtx(), last_idx=l))
        t0 = time.perf_counter()
        m = pf.lower(params, batch, last).compile().memory_analysis()
        print(f"prefill compile {time.perf_counter() - t0:.1f} s", flush=True)
        cache = on_dev(jax.eval_shape(
            lambda: init_cache(cfg, n, mix.cache_len)))
        dec = jax.jit(lambda p, c, t, pos: decode_step(p, cfg, c, t, pos,
                                                       acts, ShardCtx()))
        md = dec.lower(params, cache,
                       jax.ShapeDtypeStruct((n, 1), jnp.int32, sharding=dev),
                       jax.ShapeDtypeStruct((n,), jnp.int32, sharding=dev)
                       ).compile().memory_analysis()
        print(f"{conf['name']} {mix.name} n_slots={n} bucket={blen}: "
              f"prefill args {m.argument_size_in_bytes / gib:.2f} GiB, "
              f"out {m.output_size_in_bytes / gib:.2f} GiB, "
              f"temp {m.temp_size_in_bytes / gib:.2f} GiB; "
              f"decode args {md.argument_size_in_bytes / gib:.2f} GiB, "
              f"temp {md.temp_size_in_bytes / gib:.2f} GiB", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
