"""Compile the serving path's kernels and decode step for one TPU v5e chip.

Nothing runs: the topology is described, not attached, and the TPU
compiler checks what interpret mode cannot — tiling, SMEM/VMEM use, code
size — and whether the full-width decode step fits the chip's memory.
The topology is described inside a fixture, so collecting this file never
loads the TPU library.
"""

import base64
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compiler import compile_or_load
from repro.configs import get_config
from repro.kernels import fused as fused_mod
from repro.kernels import pack_table, ppa_eval_2d, ppa_fused_2d
from repro.kernels.fused import fused_kernel_statics
from repro.models import (ShardCtx, abstract_params, decode_step,
                          init_cache, make_model_acts, param_specs)
from repro.models.activations import ppa_table_jobs

#: HBM of one v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9

#: prefill MLP gate of internlm2-1.8b at 4 requests x 64 tokens
GATE_SHAPE = (4 * 64, 8192)

#: the same gate in the benchmark's chat cell: one 1024-token prefill,
#: flattened to the kernel's (rows, 128) tiles
CHAT_GATE_SHAPE = (1024 * 8192 // 128, 128)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _table(naf):
    """The 16-bit deployment table the served model resolves for ``naf``."""
    jobs = {n: (cfg, scheme) for n, cfg, scheme in ppa_table_jobs("ppa")}
    return pack_table(compile_or_load(naf, *jobs[naf]))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _table_args(tc, sharding):
    return (_sds(tc.starts.shape, jnp.int32, sharding),
            _sds(tc.coefs.shape, jnp.int32, sharding))


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _mosaic_text(lowered) -> str:
    """The Mosaic modules of a lowered program's kernels, as MLIR text (a
    kernel's backend config carries its module as base64 bytecode)."""
    from jax.extend.mlir import ir
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    bodies = re.findall(r"body\\22: \\22([A-Za-z0-9+/=]+)",
                        lowered.as_text())
    assert bodies, "no Mosaic kernel in the lowered program"
    return "\n".join(str(ir.Module.parse(base64.b64decode(b), context=ctx))
                     for b in bodies)


@pytest.mark.parametrize("naf,gate,min_segments", [
    ("sigmoid_wide", True, 400),    # the silu gate of the prefill MLP
    ("exp_neg", False, 400),
])
def test_fused_kernel_compiles(naf, gate, min_segments, one_chip,
                               no_persistent_cache):
    tc = _table(naf)
    assert tc.num_segments >= min_segments
    fn = jax.jit(lambda x, s, c: ppa_fused_2d(
        x, s, c, gate=gate, interpret=False, **fused_kernel_statics(tc)))
    compiled = fn.lower(_sds(GATE_SHAPE, jnp.float32, one_chip),
                        *_table_args(tc, one_chip)).compile()
    assert _has_kernel(compiled)
    assert compiled.memory_analysis().output_size_in_bytes == \
        GATE_SHAPE[0] * GATE_SHAPE[1] * 4


@pytest.mark.parametrize("naf,shape,select", [
    ("sigmoid_wide", GATE_SHAPE, "bisect"),
    ("sigmoid_wide", CHAT_GATE_SHAPE, "bisect"),
    ("exp2_frac", GATE_SHAPE, "sweep"),     # the softmax's 14 segments
])
def test_fused_kernel_select_compiles(naf, shape, select, one_chip,
                                      no_persistent_cache):
    """The 461-segment gate compiles on the blocked bisection, whose lane
    gathers Mosaic lowers to ``tpu.dynamic_gather``; the 14-segment exp2
    table compiles on the comparator sweep, with no gather."""
    tc = _table(naf)
    gate = naf == "sigmoid_wide"
    fn = jax.jit(lambda x, s, c: ppa_fused_2d(
        x, s, c, gate=gate, interpret=False, **fused_kernel_statics(tc)))
    lowered = fn.lower(_sds(shape, jnp.float32, one_chip),
                       *_table_args(tc, one_chip))
    compiled = lowered.compile()
    assert _has_kernel(compiled)
    assert f"ppa_fused_{select}" in compiled.as_text()
    gathers = _mosaic_text(lowered).count("tpu.dynamic_gather")
    assert (gathers > 0) == (select == "bisect"), gathers


def test_int_kernel_compiles(one_chip, no_persistent_cache):
    tc = _table("sigmoid_wide")
    fn = jax.jit(lambda x, s, c: ppa_eval_2d(x, s, c, tc.plan,
                                             interpret=False))
    compiled = fn.lower(_sds(GATE_SHAPE, jnp.int32, one_chip),
                        *_table_args(tc, one_chip)).compile()
    assert _has_kernel(compiled)


def test_decode_step_fits_one_chip(one_chip, no_persistent_cache,
                                   monkeypatch):
    """Full-width internlm2-1.8b decode step on the fused kernel backend:
    compiles for one chip and fits its memory."""
    # the platform here is the CPU, which would interpret the kernel;
    # compile it as the chip runs it
    monkeypatch.setattr(fused_mod, "resolve_interpret",
                        lambda interpret: False)
    cfg = get_config("internlm2-1.8b").replace(act_backend="pallas_fused")
    acts = make_model_acts(cfg)
    n_slots, cache_len = 4, 256

    def put(tree):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), tree)

    params = put(abstract_params(param_specs(cfg)))
    cache = put(jax.eval_shape(lambda: init_cache(cfg, n_slots, cache_len)))
    fn = jax.jit(lambda p, c, t, pos: decode_step(p, cfg, c, t, pos, acts,
                                                  ShardCtx()))
    compiled = fn.lower(params, cache,
                        _sds((n_slots, 1), jnp.int32, one_chip),
                        _sds((n_slots,), jnp.int32, one_chip)).compile()
    assert _has_kernel(compiled)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem
