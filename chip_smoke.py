"""Smoke run of the serving path on the chip.

One chip (the default): internlm2-1.8b at its published width, every
nonlinearity through the paper's 16-bit FQA-O2 tables, compiled into an
empty table store and served through ``ServeEngine`` twice on the same
weights — on the ``ref`` activation backend, then on the compiled
``pallas_fused`` kernel.  Every request must finish with finite logits, and
the two runs must give bit-identical logits and tokens.

``--chips 4``: qwen2-7b at its published width, tensor-parallel over a
(data=1, model=4) mesh of the host's four chips with the serving sharding
rules: one prefill and four decode steps on four requests.  A 2-layer depth
cut of the same seeded model, in float32, then runs unsharded on one chip
and sharded on the mesh, and the two must agree.

Run from the repository root::

    python chip_smoke.py [--chips 4]

Phase timings and peak device memory go to earlier lines; they are
information from one run, not measurements.  The last line of stdout is
one JSON object, ``{"ok": true, "device": {...}}``.  Without a TPU the
script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compiler import CompileJob, TableStore, compile_batch  # noqa: E402
from repro.configs import get_config, resolve_for_mesh  # noqa: E402
from repro.distributed import (cache_shardings, make_ctx, make_rules,  # noqa: E402
                               param_shardings)
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.models import (ShardCtx, StageCfg, decode_step,  # noqa: E402
                          init_params, make_model_acts, param_specs, prefill)
from repro.models.activations import ppa_table_jobs  # noqa: E402
from repro.runtime import enable_compile_cache  # noqa: E402
from repro.serve import Request, ServeEngine  # noqa: E402

SEED = 0
PROMPT_LENS = (16, 32, 48, 64)
MAX_NEW = 8
CACHE_LEN = 256
DECODE_STEPS = 4

#: Sharded vs unsharded depth-cut logits, as a share of the largest
#: reference logit.  The cut computes in float32 with float32 matmuls, so
#: the two runs differ only where the sharded matmuls split a contraction
#: over four chips and reorder its partial sums: about 1e-6 of the logit
#: scale.  An activation input that sits on a PPA table's 8-bit input grid
#: boundary can step to the next grid point, and random weights amplify
#: such a step through two layers; 1e-2 leaves room for that and still
#: fails a wrong sharding rule, which moves logits by their own size.
CUT_TOLERANCE = 1e-2


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def seeded_params(cfg, shardings=None):
    """``cfg``'s parameters drawn from ``SEED`` in one compiled program,
    placed by ``shardings`` (default device when None)."""
    specs = param_specs(cfg)
    return jax.jit(lambda k: init_params(specs, k),
                   out_shardings=shardings)(jax.random.PRNGKey(SEED))


def prompts_for(vocab: int, seed: int = SEED):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in PROMPT_LENS]


# ------------------------------------------------------------------ one chip
def compile_tables(impl: str, root: str) -> TableStore:
    """Compile the deployment's tables into the empty store at ``root``
    and resolve each through ``compile_or_load``."""
    store = TableStore(root)
    triples = ppa_table_jobs(impl)
    t0 = time.perf_counter()
    compile_batch([CompileJob(n, c, s) for n, c, s in triples], store=store)
    for naf, cfg, scheme in triples:
        tab = store.compile_or_load(naf, cfg, scheme)
        log(f"table {naf}: {tab.num_segments} segments")
    if store.compiles != len(triples):
        raise RuntimeError(f"store compiled {store.compiles} tables, "
                           f"expected {len(triples)} from empty")
    log(f"tables: {store.compiles} compiled in "
        f"{time.perf_counter() - t0:.2f} s")
    return store


class _RecordingEngine(ServeEngine):
    """Keeps every logits batch the engine samples from, tagged with the
    phase (prefill admission or decode step) that produced it."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.seen = []
        self._phase = "decode"

    def _admit(self) -> None:
        self._phase = "prefill"
        super()._admit()
        self._phase = "decode"

    def _sample_rows(self, logits, temps, keys):
        self.seen.append((self._phase, np.asarray(logits)))
        return super()._sample_rows(logits, temps, keys)


def serve(cfg, params, store: TableStore, backend: str, prompts):
    """Serve ``prompts`` greedily; returns (outputs, logits batches)."""
    eng = _RecordingEngine(cfg, params, n_slots=len(prompts),
                           cache_len=CACHE_LEN, table_store=store,
                           act_backend=backend)
    # the admissions below group as buckets 16, 32 (one prompt each) and
    # 64 (two prompts): trace exactly those shapes and the decode step
    t0 = time.perf_counter()
    eng.warmup((16, 32), batch=1, decode=False)
    eng.warmup((64,), batch=2, decode=True)
    log(f"{backend}: compile {time.perf_counter() - t0:.2f} s "
        f"({eng.prefill_retraces} prefill shapes + decode)")
    traced = eng.prefill_retraces
    eng.seen.clear()

    reqs = [Request(rid=i, prompt=p, max_new_tokens=MAX_NEW)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    for r in reqs:
        if not eng.submit(r):
            raise RuntimeError(f"request {r.rid} shed: {r.rejected}")
    eng.run_until_drained(max_steps=4 * MAX_NEW)
    log(f"{backend}: served {len(reqs)} requests x {MAX_NEW} tokens in "
        f"{time.perf_counter() - t0:.2f} s")
    if eng.prefill_retraces != traced:
        raise RuntimeError("a prefill shape compiled inside the run")
    for r in reqs:
        if not r.done or r.timed_out or len(r.output) != MAX_NEW:
            raise RuntimeError(f"{backend}: request {r.rid} ended with "
                               f"{len(r.output)} tokens (done={r.done})")
    for phase, lg in eng.seen:
        if not np.isfinite(lg.astype(np.float32)).all():
            raise RuntimeError(f"{backend}: non-finite {phase} logits")
    log(f"{backend}: peak device memory "
        f"{peak_bytes(jax.devices()[0])} bytes")
    return [list(r.output) for r in reqs], eng.seen


def check_parity(ref, fused) -> None:
    """Bit-identical tokens and logits between the two backends."""
    (ref_out, ref_seen), (fu_out, fu_seen) = ref, fused
    if [p for p, _ in ref_seen] != [p for p, _ in fu_seen]:
        raise RuntimeError("backends sampled different logits batches")
    n_pre = 0
    for (phase, a), (_, b) in zip(ref_seen, fu_seen):
        if a.shape != b.shape or a.tobytes() != b.tobytes():
            diff = np.abs(a.astype(np.float32) - b.astype(np.float32)).max()
            raise RuntimeError(f"{phase} logits differ between ref and "
                               f"pallas_fused (max |diff| {diff})")
        n_pre += phase == "prefill"
    if ref_out != fu_out:
        raise RuntimeError(f"tokens differ: {ref_out} vs {fu_out}")
    log(f"parity: {n_pre} prefill and {len(ref_seen) - n_pre} decode "
        f"logits batches and {sum(map(len, ref_out))} tokens bit-identical")


def one_chip(cfg) -> None:
    log(f"model {cfg.arch}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, act_impl {cfg.act_impl}")
    with tempfile.TemporaryDirectory(prefix="smoke-tables-") as root:
        store = compile_tables(cfg.act_impl, root)
        t0 = time.perf_counter()
        params = seeded_params(cfg)
        jax.block_until_ready(params)
        log(f"params: {sum(x.size for x in jax.tree.leaves(params))} "
            f"float32 in {time.perf_counter() - t0:.2f} s")
        prompts = prompts_for(cfg.vocab)
        ref = serve(cfg, params, store, "ref", prompts)
        fused = serve(cfg, params, store, "pallas_fused", prompts)
    check_parity(ref, fused)


# ---------------------------------------------------------------- four chips
def prefill_decode(cfg, params, ctx: ShardCtx, prompts, mesh=None,
                   feed=None):
    """One batched prefill of ``prompts`` (right-padded, read at each row's
    last real token) and ``DECODE_STEPS`` decode steps, each fed the
    greedy token of the step before or, given ``feed``, that token list.
    With a mesh the decode cache carries the serving cache shardings.
    Returns the prefill logits and the stacked decode logits as float32
    numpy, and the tokens fed."""
    acts = make_model_acts(cfg)
    b, t = len(prompts), max(len(p) for p in prompts)
    toks = np.zeros((b, t), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lens = np.asarray([len(p) for p in prompts], np.int32)

    def pf(p, tk, last):
        return prefill(p, cfg, {"tokens": tk}, CACHE_LEN, acts, ctx,
                       last_idx=last)

    def dec(p, c, tk, pos):
        return decode_step(p, cfg, c, tk, pos, acts, ctx)

    if mesh is None:
        pf_j, dec_j = jax.jit(pf), jax.jit(dec)
    else:
        cache_abs = jax.eval_shape(pf, params, toks, lens - 1)[1]
        cshard = cache_shardings(mesh, cache_abs, kv_shard=cfg.kv_shard)
        pf_j = jax.jit(pf, out_shardings=(None, cshard))
        dec_j = jax.jit(dec, out_shardings=(None, cshard))
    logits, cache = pf_j(params, jnp.asarray(toks), jnp.asarray(lens - 1))
    pos = jnp.asarray(lens)
    fed, steps = [], []
    for i in range(DECODE_STEPS):
        prev = logits if i == 0 else steps[-1]
        tok = (np.asarray(jnp.argmax(prev, axis=-1), np.int32)
               if feed is None else feed[i])
        fed.append(tok)
        lg, cache = dec_j(params, cache, jnp.asarray(tok)[:, None], pos)
        steps.append(lg)
        pos = pos + 1
    out = (np.asarray(logits, np.float32),
           np.stack([np.asarray(s, np.float32) for s in steps]))
    for name, a in zip(("prefill", "decode"), out):
        if not np.isfinite(a).all():
            raise RuntimeError(f"non-finite {name} logits")
    return out + (fed,)


def sharded_params(cfg, mesh, params=None):
    """Serving-rule shardings of ``cfg``'s parameters on ``mesh``: places
    ``params``, or draws them sharded from ``SEED``."""
    shard = param_shardings(param_specs(cfg), mesh, make_rules("serve", mesh))
    if params is not None:
        return jax.device_put(params, shard)
    return seeded_params(cfg, shard)


def four_chips(devices, cfg) -> None:
    mesh = make_local_mesh(4)
    ctx = make_ctx(mesh)
    cfg = resolve_for_mesh(cfg, tp=mesh.shape["model"])
    log(f"mesh {dict(mesh.shape)}; model {cfg.arch}: {cfg.n_layers} layers, "
        f"d_model {cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
        f"padding {list(cfg.pad_info)}")
    prompts = prompts_for(cfg.vocab)

    t0 = time.perf_counter()
    params = sharded_params(cfg, mesh)
    jax.block_until_ready(params)
    log(f"params: {sum(x.size for x in jax.tree.leaves(params))} float32 "
        f"sharded in {time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    pf, dec, _ = prefill_decode(cfg, params, ctx, prompts, mesh)
    log(f"full depth, sharded: prefill {pf.shape} + {len(dec)} decode "
        f"steps finite, compile+run {time.perf_counter() - t0:.2f} s")
    del params

    cut = cfg.replace(stages=(StageCfg("dec", 2),), compute_dtype="float32")
    cut_params = seeded_params(cut)
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        ref_pf, ref_dec, fed = prefill_decode(cut, cut_params, ShardCtx(),
                                              prompts)
        log(f"2-layer cut, one chip: {time.perf_counter() - t0:.2f} s")
        # the sharded run decodes the reference run's tokens: a near-tie
        # argmax flipped by rounding would otherwise compare different
        # sequences
        t0 = time.perf_counter()
        sh_pf, sh_dec, _ = prefill_decode(
            cut, sharded_params(cut, mesh, cut_params), ctx, prompts, mesh,
            feed=fed)
        log(f"2-layer cut, sharded: {time.perf_counter() - t0:.2f} s")
    for name, a, b in (("prefill", ref_pf, sh_pf), ("decode", ref_dec, sh_dec)):
        rel = float(np.abs(a - b).max() / np.abs(a).max())
        log(f"2-layer cut {name}: max |sharded - unsharded| / max |logit| "
            f"= {rel:.3e} (tolerance {CUT_TOLERANCE})")
        if not rel <= CUT_TOLERANCE:
            raise RuntimeError(f"sharded {name} logits off by {rel:.3e}")
    for d in devices[:4]:
        log(f"peak device memory {d.id}: {peak_bytes(d)} bytes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    d0 = devices[0]
    log(f"devices: {devices} platform={d0.platform} "
        f"kind={d0.device_kind} count={len(devices)}")
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU here (platform {d0.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    log(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        four_chips(devices, get_config("qwen2-7b"))
    else:
        one_chip(get_config("internlm2-1.8b"))
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
