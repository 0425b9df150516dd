import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The three lines above MUST stay the first statements in this module — jax
locks the platform and device count at first backend init, and the
production meshes need 512 host-platform placeholder devices (on a TPU
host the dry run must not take the chip).  Nothing here allocates a
buffer: parameters, optimizer state, caches and batches are all
ShapeDtypeStructs; ``.lower().compile()`` exercises the full GSPMD
partitioner + XLA pipeline, and the compiled artifact yields
memory_analysis / cost_analysis / the collective schedule for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all [--multi-pod]
  python -m repro.launch.dryrun --list
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import (ARCH_IDS, SHAPES, apply_shape, get_config,
                           resolve_for_mesh, shape_skip_reason)
from repro.distributed import (batch_shardings, cache_shardings, make_ctx,
                               make_rules, param_shardings)
from repro.launch.mesh import make_production_mesh, mesh_desc
from repro.launch.specs import active_params, input_specs
from repro.models import (ModelCfg, abstract_params, count_params,
                          decode_step, make_model_acts, param_specs, prefill)
from repro.roofline import analyze_compiled
from repro.train import OptCfg, TrainCfg, make_train_step, train_init

ART_DIR = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _attn_flops(cfg: ModelCfg, shape) -> float:
    """Attention score/value matmul FLOPs (unpadded dims, fwd)."""
    b, t = shape.global_batch, shape.seq_len
    hq, dh = cfg.n_q, cfg.head_dim
    total = 0.0
    for st in cfg.stages:
        if st.kind in ("dec", "xdec", "hyb", "enc"):
            if shape.kind == "decode":
                s_eff = min(t, st.window or t)
                total += 4.0 * b * st.n_layers * s_eff * hq * dh
            else:
                s_eff = min(t, st.window or t)
                # causal: sum over rows of min(row, window) ~ t*s_eff - s^2/2
                pairs = t * s_eff - (s_eff * s_eff) / 2
                total += 4.0 * b * st.n_layers * pairs * hq * dh
    return total


def cell_model_flops(cfg_unpadded: ModelCfg, shape) -> float:
    n_active = active_params(cfg_unpadded,
                             abstract_params(param_specs(cfg_unpadded)))
    if shape.kind == "train":
        base = 6.0 * n_active * shape.global_batch * shape.seq_len
        return base + 3.0 * _attn_flops(cfg_unpadded, shape)
    if shape.kind == "prefill":
        base = 2.0 * n_active * shape.global_batch * shape.seq_len
        return base + _attn_flops(cfg_unpadded, shape)
    base = 2.0 * n_active * shape.global_batch
    return base + _attn_flops(cfg_unpadded, shape)


VARIANTS = {
    "baseline": {},
    # beyond-paper activation deployment modes (bit-exact; DESIGN.md §3)
    "lut_index": {"act_backend": "lut_index"},
    "lut_value": {"act_backend": "lut_value"},
    # fused float->PPA->float activation kernel (one pallas_call, incl.
    # silu/gelu gating; kernels/fused.py)
    "fused": {"act_backend": "pallas_fused"},
    # flash-decode-style KV: cache seq-sharded, kv heads unpadded
    "kvseq": {"kv_shard": "seq"},
    # exact float activations (ablation: PPA overhead isolation)
    "exact": {"act_impl": "exact"},
    # weight-stationary decode: no FSDP on dense weights (profile-level)
    "wstation": {"_profile": "serve_wstation"},
    # bf16 parameter storage (serving: halves weight reads, elides the
    # per-step f32->bf16 cast)
    "bf16w": {"param_dtype": "bfloat16"},
    # microbatch gradient accumulation (train peak-memory envelope)
    "accum4": {"_accum": 4},
    # larger flash KV chunk (fewer online-softmax rescale passes)
    "bigchunk": {"flash_chunk": 4096},
    # chunked online-softmax attention for training shapes too
    "flash": {"attn_impl": "flash"},
}


def _parse_variant(variant: str) -> dict:
    kw = {}
    for part in variant.split("+"):
        kw.update(VARIANTS[part])
    return kw


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               variant: str = "baseline"):
    """Build + lower + compile one cell; returns (compiled, meta)."""
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    tp = mesh.shape["model"]
    cfg0 = get_config(arch)
    overrides = _parse_variant(variant)
    profile_override = overrides.pop("_profile", None)
    accum = overrides.pop("_accum", 1)
    cfg = apply_shape(resolve_for_mesh(cfg0.replace(**overrides), tp=tp),
                      shape)
    batch_sharded = shape.global_batch >= 8   # long_500k (B=1): replicate
    ctx = make_ctx(mesh, batch_sharded=batch_sharded)

    profile = profile_override or (
        "train" if shape.kind == "train" else "serve")
    rules = make_rules(profile, mesh,
                       kv_heads_sharded=cfg.kv_shard != "seq")
    specs = param_specs(cfg)
    params_abs = abstract_params(specs, jnp.dtype(cfg.param_dtype))
    pshard = param_shardings(specs, mesh, rules)
    params_abs = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        params_abs, pshard)
    n_params = count_params(params_abs)

    ins = input_specs(cfg, shape, mesh, batch_sharded)
    acts = make_model_acts(cfg)

    if shape.kind == "train":
        okind = "adafactor" if n_params > 1e11 else "adamw"
        tcfg = TrainCfg(opt=OptCfg(kind=okind), accum_steps=accum)
        step = make_train_step(cfg, tcfg, ctx)
        tstate_abs = jax.eval_shape(
            lambda p: train_init(tcfg, p), params_abs)
        fn = jax.jit(step, donate_argnums=(0, 1))
        args = (params_abs, tstate_abs, ins)
    elif shape.kind == "prefill":
        def pf(params, batch):
            return prefill(params, cfg, batch, shape.seq_len, acts, ctx)
        fn = jax.jit(pf)
        args = (params_abs, ins)
    else:
        cache_abs = ins.pop("cache")
        cshard = cache_shardings(mesh, cache_abs, batch_sharded,
                                 kv_shard=cfg.kv_shard)
        cache_abs = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            cache_abs, cshard)

        def dec(params, cache, tokens, pos):
            return decode_step(params, cfg, cache, tokens, pos, acts, ctx)
        fn = jax.jit(dec, donate_argnums=(1,))
        args = (params_abs, cache_abs, ins["tokens"], ins["pos"])

    # decode scores against the bandwidth roof: active params + KV cache
    # read exactly once per step
    ideal_bytes = 0.0
    if shape.kind == "decode":
        import numpy as np
        from repro.models import tree_bytes
        n_active = active_params(cfg0, abstract_params(param_specs(cfg0)))
        pbytes = jnp.dtype(cfg.param_dtype).itemsize
        ideal_bytes = n_active * pbytes + tree_bytes(cache_abs)

    t0 = time.time()
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    meta = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh": mesh_desc(mesh), "chips": mesh.size,
        "n_params": n_params,
        "model_flops": cell_model_flops(cfg0, shape),
        "ideal_bytes": ideal_bytes,
        "pad_info": list(cfg.pad_info),
        "t_lower_s": t_lower, "t_compile_s": t_compile,
        "profile": profile, "optimizer": (okind if shape.kind == "train"
                                          else None),
    }
    return compiled, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Path = ART_DIR, verbose: bool = True,
             variant: str = "baseline") -> dict:
    skip = shape_skip_reason(arch, shape_name)
    tag = "multipod" if multi_pod else "pod"
    if variant != "baseline":
        tag = f"{tag}__{variant}"
    rec: dict
    if skip:
        rec = {"arch": arch, "shape": shape_name, "mesh": tag,
               "status": "skip", "reason": skip}
    else:
        compiled, meta = lower_cell(arch, shape_name, multi_pod, variant)
        try:
            ma = compiled.memory_analysis()
            mem = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "peak_bytes_per_device": int(ma.argument_size_in_bytes
                                             + ma.temp_size_in_bytes),
            }
        except Exception as e:  # backend without memory_analysis
            mem = {"error": str(e)}
        rl = analyze_compiled(
            compiled, arch=arch, shape=shape_name, mesh_desc=meta["mesh"],
            chips=meta["chips"], model_fl=meta["model_flops"],
            ideal_bytes=meta["ideal_bytes"])
        rec = {"status": "ok", **meta, "memory": mem,
               "roofline": rl.as_dict()}
        if verbose:
            print(f"[{arch} x {shape_name} x {tag}] "
                  f"compile {meta['t_compile_s']:.1f}s  "
                  f"params {meta['n_params']/1e9:.2f}B  "
                  f"args/dev {mem.get('argument_bytes', 0)/2**30:.2f}GiB  "
                  f"bottleneck {rl.bottleneck}  "
                  f"roofline_frac {rl.roofline_fraction:.3f}")
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{arch}__{shape_name}__{tag}.json"
    path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    help="'+'-joined subset of " + ",".join(VARIANTS))
    ap.add_argument("--out", default=str(ART_DIR))
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        for a in ARCH_IDS:
            for s in SHAPES:
                skip = shape_skip_reason(a, s)
                print(f"{a:24s} {s:12s} {'SKIP: ' + skip if skip else 'run'}")
        return

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    failures = []
    for a in archs:
        for s in shapes:
            try:
                run_cell(a, s, args.multi_pod, Path(args.out),
                         variant=args.variant)
            except Exception:
                failures.append((a, s))
                traceback.print_exc()
    if failures:
        raise SystemExit(f"FAILED cells: {failures}")
    print("dry-run complete")


if __name__ == "__main__":
    main()
