"""GPipe-style pipeline parallelism over the "pod" mesh axis.

At multi-pod scale the inter-pod links are the scarcest resource; pipeline
parallelism sends only microbatch activations across pods instead of
gradient/weight traffic.  Implementation: shard_map manual over "pod"
(everything else stays GSPMD-auto), layers of one scanned stack split
evenly into ``n_stages`` contiguous stages, jax.lax.ppermute moves
activations stage -> stage+1, and the classic (n_micro + n_stages - 1)
rotation schedule keeps every stage busy after the fill phase.

The stage's layer params arrive already sliced (the "layers" dim of every
stacked param is sharded over "pod" at the jit boundary), so weights never
move.  Bubble fraction = (S-1)/(M+S-1) — reported by ``bubble_fraction``.

This module is exercised by dense-arch multi-pod profiles and tested on a
host-platform mesh in tests/test_distributed.py; MoE archs keep pod=DP
(their shard_map MoE block composes with auto axes, not with manual pod).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS


__all__ = ["pipeline_apply", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(
    body: Callable,          # body(h, layer_params) -> h  (one layer)
    stack_params,            # pytree; leaves (L, ...) with L % n_stages == 0
    h: jax.Array,            # (B, T, D) stage input (full batch)
    mesh,
    *,
    n_micro: int,
    axis: str = "pod",
):
    """Run a scanned layer stack as a pipeline over ``axis``.

    h is batch-split into ``n_micro`` microbatches; every stage scans its
    own L/S layers per microbatch; ppermute rotates the microbatch ring.
    """
    n_stages = mesh.shape[axis]
    b = h.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    assert n_micro % n_stages == 0, \
        "n_micro must be a multiple of n_stages (ring schedule)"

    def stage_fn(stack_local, h_all):
        stage = jax.lax.axis_index(axis)
        mb = jnp.stack(jnp.split(h_all, n_micro, axis=0))  # (M, b/M, T, D)

        def run_stage(x):
            def f(carry, lp):
                return body(carry, lp), None
            out, _ = jax.lax.scan(f, x, stack_local)
            return out

        # rotation schedule: at tick t, this stage works on microbatch
        # (t - stage) mod M if 0 <= t - stage < M; results collected into
        # the output buffer at the same index once the last stage ran it.
        total = n_micro + n_stages - 1
        out_buf = jnp.zeros_like(mb)
        # the ring register holding the activation travelling through
        reg = jnp.zeros_like(mb[0])

        def tick(carry, t):
            reg, out_buf = carry
            my_mb = t - stage
            take = (my_mb >= 0) & (my_mb < n_micro)
            # stage 0 loads a fresh microbatch from its local buffer
            idx = jnp.clip(my_mb, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(mb, idx, 0, keepdims=False)
            x_in = jnp.where(stage == 0, fresh, reg)
            y = run_stage(x_in)
            y = jnp.where(take[..., None, None, None]
                          if y.ndim == 3 else take, y, reg)
            # last stage stores its finished microbatch
            is_last = stage == n_stages - 1
            store = take & is_last
            out_buf = jax.lax.cond(
                store,
                lambda ob: jax.lax.dynamic_update_index_in_dim(
                    ob, y, idx, 0),
                lambda ob: ob, out_buf)
            # rotate: stage s sends to s+1 (last sends to 0, discarded)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            reg = jax.lax.ppermute(y, axis, perm)
            return (reg, out_buf), None

        (reg, out_buf), _ = jax.lax.scan(
            tick, (reg, out_buf), jnp.arange(total))
        # every stage holds out_buf; only last stage's is real -> broadcast
        out_buf = jax.lax.psum(
            jnp.where(stage == n_stages - 1, out_buf, 0.0), axis)
        return out_buf.reshape(h_all.shape)

    pspec = jax.tree_util.tree_map(lambda _: PS(axis), stack_params)
    return jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(pspec, PS()),      # params: layers sharded; h replicated
        out_specs=PS(),
        check_vma=False,
    )(stack_params, h)
