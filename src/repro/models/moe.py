"""Mixture-of-Experts block (moonshot 64e/top-6, kimi-k2 384e/top-8).

Dispatch is sort-free and capacity-bounded — no (S, E, C) one-hot tensor
(which at kimi scale would be ~85 TB): per top-k slice we compute each
token's position inside its expert's buffer with a (S, E_loc+1) one-hot
cumsum, then use one batched scatter into the (E_loc, C, d) buffer and one
batched fill-gather back.  O(S*k*d + E*C*d) memory, MXU-friendly batched
expert matmuls.

Distribution (shard_map, manual over every mesh axis):

  mode="weight_gather" (train / prefill — token-heavy):
    experts sharded over "model" (EP); expert weights additionally FSDP-
    sharded over the dp axes on d and all-gathered per layer; tokens stay
    in their data shard (each expert is evaluated per data shard on that
    shard's tokens — no token all-to-all at all); outputs psum over
    "model".

  mode="token_gather" (decode — weight-heavy):
    expert weights stay fully sharded (E over "model", f over dp axes);
    the (tiny) decode token batch is all-gathered over dp, every chip
    computes its (E_loc, f_loc) partial, and one psum over all axes
    rebuilds the outputs.  Zero weight movement per step — exactly what a
    1T-param MoE needs at decode time.

With ``ctx.mesh is None`` the same dispatch core runs locally (E_loc = E,
no collectives) — bit-identical math, used by smoke tests.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS


from .activations import ActBundle
from .common import P, ShardCtx
from .mlp import gated_mlp, gated_mlp_params

__all__ = ["MoECfg", "moe_params", "moe_block"]


@dataclasses.dataclass(frozen=True)
class MoECfg:
    d_model: int
    d_ff: int                      # per-expert hidden
    n_experts: int
    top_k: int
    router_score: str = "softmax"  # softmax | sigmoid (deepseek/kimi style)
    capacity_factor: float = 1.25
    gate: str = "silu"
    n_shared: int = 0              # shared (always-on) experts
    aux_coef: float = 0.01
    mode: str = "weight_gather"    # weight_gather | token_gather


def moe_params(cfg: MoECfg, layers: Optional[int] = None) -> dict:
    def lp(shape, axes, **kw):
        if layers is None:
            return P(shape, axes, **kw)
        return P((layers,) + shape, ("layers",) + axes, **kw)

    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    out = {
        "router": lp((d, e), (None, None)),   # small; replicated
        "w_gate": lp((e, d, f), ("expert", "expert_embed", "expert_mlp")),
        "w_up": lp((e, d, f), ("expert", "expert_embed", "expert_mlp")),
        "w_down": lp((e, f, d), ("expert", "expert_mlp", "expert_embed")),
    }
    if cfg.n_shared:
        out["shared"] = gated_mlp_params(d, f * cfg.n_shared, layers)
    return out


def _route(x2: jax.Array, router: jax.Array, cfg: MoECfg
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(S, d) -> top-k ids (S,k), weights (S,k), aux loss scalar."""
    logits = jnp.einsum("sd,de->se", x2.astype(jnp.float32),
                        router.astype(jnp.float32))
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        probs = jax.nn.softmax(logits, axis=-1)   # aux loss uses probs
    else:
        scores = probs = jax.nn.softmax(logits, axis=-1)
    vals, ids = jax.lax.top_k(scores, cfg.top_k)
    wts = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)

    # switch-style load-balance loss
    e = cfg.n_experts
    assign = jax.nn.one_hot(ids, e, dtype=jnp.float32).sum(1)     # (S, e)
    f_e = assign.mean(0) / cfg.top_k
    p_e = probs.mean(0)
    aux = cfg.aux_coef * e * jnp.sum(f_e * p_e)
    return ids.astype(jnp.int32), wts.astype(x2.dtype), aux


def _dispatch_compute(x2, ids_loc, wts, wg, wu, wd, e_loc: int, cap: int,
                      acts: ActBundle, gate: str):
    """Core: scatter tokens into expert buffers, run experts, combine.

    ids_loc in [0, e_loc) for local assignments, == e_loc for remote/invalid
    (dropped by out-of-bounds scatter/gather semantics).
    """
    s, d = x2.shape
    k = ids_loc.shape[1]
    counts = jnp.zeros((e_loc + 1,), jnp.int32)
    buf = jnp.zeros((e_loc, cap, d), x2.dtype)
    les, poss = [], []
    for j in range(k):
        le = ids_loc[:, j]
        oh = jax.nn.one_hot(le, e_loc + 1, dtype=jnp.int32)
        within = jnp.cumsum(oh, axis=0) - 1                     # (S, e+1)
        pos = jnp.take(counts, le) + jnp.take_along_axis(
            within, le[:, None], axis=1)[:, 0]
        counts = counts + oh.sum(0)
        buf = buf.at[le, pos].set(x2, mode="drop")
        les.append(le)
        poss.append(pos)

    h = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    y_e = jnp.einsum("ecf,efd->ecd", acts.gate(gate)(h) * u, wd)

    y = jnp.zeros_like(x2)
    for j in range(k):
        g = y_e.at[les[j], poss[j]].get(mode="fill", fill_value=0)
        y = y + wts[:, j:j + 1] * g
    return y


def _capacity(tokens: int, cfg: MoECfg) -> int:
    c = int(tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, (c + 7) // 8 * 8)


def moe_block(params: dict, x: jax.Array, cfg: MoECfg, acts: ActBundle,
              ctx: ShardCtx) -> Tuple[jax.Array, jax.Array]:
    """(B, T, D) -> (B, T, D), aux-loss scalar."""
    b, t, d = x.shape

    if ctx.mesh is None:
        x2 = x.reshape(b * t, d)
        ids, wts, aux = _route(x2, params["router"], cfg)
        cap = _capacity(b * t, cfg)
        y = _dispatch_compute(x2, ids, wts, params["w_gate"],
                              params["w_up"], params["w_down"],
                              cfg.n_experts, cap, acts, cfg.gate)
        y = y.reshape(b, t, d)
    else:
        y, aux = _moe_sharded(params, x, cfg, acts, ctx)

    if cfg.n_shared:
        y = y + gated_mlp(params["shared"], x, acts, ctx, cfg.gate)
    return y, aux


# ------------------------------------------------------------- shard_map
def _moe_sharded(params, x, cfg: MoECfg, acts, ctx: ShardCtx):
    mesh = ctx.mesh
    dp = tuple(a for a in ctx.dp_axes if a in mesh.axis_names)
    tp = ctx.tp_axis
    bspec = dp if (ctx.batch_sharded and dp) else None
    e_loc = cfg.n_experts // mesh.shape[tp]

    if cfg.mode == "weight_gather":
        wspec = PS(tp, dp, None)         # (E, d, f): E->model, d->fsdp
        dspec = PS(tp, None, dp)         # (E, f, d)
    else:
        wspec = PS(tp, None, dp)         # (E, d, f): f->fsdp (stationary)
        dspec = PS(tp, dp, None)

    in_specs = (PS(None, None),          # router (replicated)
                wspec, wspec, dspec,
                PS(bspec, None, None))   # x
    out_specs = (PS(bspec, None, None), PS())

    fn = functools.partial(_moe_body, cfg=cfg, acts=acts, e_loc=e_loc,
                           dp=dp, tp=tp, batch_sharded=bool(bspec))
    y, aux = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_up"], params["w_down"],
      x)
    return y, aux


def _moe_body(router, wg, wu, wd, x, *, cfg: MoECfg, acts, e_loc, dp, tp,
              batch_sharded):
    b, t, d = x.shape
    e0 = jax.lax.axis_index(tp) * e_loc

    if cfg.mode == "weight_gather":
        # FSDP gather of this layer's local experts over the dp axes
        if dp:
            wg = jax.lax.all_gather(wg, dp, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, dp, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, dp, axis=2, tiled=True)
        x2 = x.reshape(b * t, d)
        ids, wts, aux = _route(x2, router, cfg)
        ids_loc = jnp.where((ids >= e0) & (ids < e0 + e_loc),
                            ids - e0, e_loc)
        cap = _capacity(b * t, cfg)
        y = _dispatch_compute(x2, ids_loc, wts, wg, wu, wd, e_loc, cap,
                              acts, cfg.gate)
        y = jax.lax.psum(y, tp)
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return y.reshape(b, t, d), aux

    # token_gather: weights stationary (f sharded over dp), tokens gathered
    if dp and batch_sharded:
        xg = jax.lax.all_gather(x, dp, axis=0, tiled=True)
    else:
        xg = x
    bg = xg.shape[0]
    x2 = xg.reshape(bg * t, d)
    ids, wts, aux = _route(x2, router, cfg)
    ids_loc = jnp.where((ids >= e0) & (ids < e0 + e_loc), ids - e0, e_loc)
    cap = _capacity(bg * t, cfg)
    y = _dispatch_compute(x2, ids_loc, wts, wg, wu, wd, e_loc, cap,
                          acts, cfg.gate)
    axes = (tp,) + tuple(dp)
    y = jax.lax.psum(y, axes)            # full (Bg*T, d) everywhere
    y = y.reshape(bg, t, d)
    if dp and batch_sharded:
        row = jax.lax.axis_index(dp[0]) if len(dp) == 1 else (
            jax.lax.axis_index(dp[0]) * jax.lax.axis_size(dp[1])
            + jax.lax.axis_index(dp[1]))
        y = jax.lax.dynamic_slice_in_dim(y, row * b, b, axis=0)
    return y, aux
