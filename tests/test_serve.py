"""Serving engine: slot scheduling, drain, greedy-consistency vs a
hand-rolled prefill+decode loop, coalesced-vs-serial token bit-identity,
retrace bounding, and the tenant front's pin/evict contract."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler import CompileJob, TableStore
from repro.configs import get_smoke_config
from repro.models import (ShardCtx, decode_step, init_params,
                          make_model_acts, param_specs, ppa_table_jobs,
                          prefill)
from repro.serve import Request, ServeEngine, TenantFront, TenantSpec


@functools.lru_cache(maxsize=None)
def _setup(arch="internlm2-1.8b"):
    cfg = get_smoke_config(arch)
    params = init_params(param_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _mixed_requests(cfg, lens, *, max_new=4, temps=None, seed=7):
    """One request per entry of ``lens`` (temperature cycled from temps)."""
    rng = np.random.default_rng(seed)
    out = []
    for i, lp in enumerate(lens):
        t = 0.0 if temps is None else temps[i % len(temps)]
        out.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, lp).astype(np.int32),
            max_new_tokens=max_new, temperature=t))
    return out


def test_engine_drains_and_lengths():
    cfg, params = _setup()
    eng = ServeEngine(cfg, params, n_slots=2, cache_len=48)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                    max_new_tokens=5)
            for i in range(5)]          # 5 requests through 2 slots
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert all(r.done for r in reqs)
    assert all(len(r.output) == 5 for r in reqs)


def test_engine_greedy_matches_manual_loop():
    cfg, params = _setup()
    acts = make_model_acts(cfg)
    ctx = ShardCtx()
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab, 8).astype(np.int32)

    # manual greedy loop (batch 1)
    logits, cache = prefill(params, cfg, {"tokens": jnp.asarray(prompt[None])},
                            cache_len=48, acts=acts, ctx=ctx)
    toks = [int(jnp.argmax(logits[0]))]
    pos = 8
    for _ in range(4):
        lg, cache = decode_step(params, cfg, cache,
                                jnp.asarray([[toks[-1]]], jnp.int32),
                                jnp.asarray([pos], jnp.int32), acts, ctx)
        toks.append(int(jnp.argmax(lg[0])))
        pos += 1

    eng = ServeEngine(cfg, params, n_slots=2, cache_len=48)
    req = Request(rid=0, prompt=prompt, max_new_tokens=5)
    eng.submit(req)
    eng.run_until_drained()
    assert req.output == toks


def test_engine_slot_reuse_no_crosstalk():
    """A request admitted into a freed slot must not see stale cache."""
    cfg, params = _setup()
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab, 8).astype(np.int32)

    # run the same prompt twice: once in a fresh engine, once after the
    # slot was used by a different request
    ref_eng = ServeEngine(cfg, params, n_slots=1, cache_len=48)
    ref = Request(rid=0, prompt=prompt, max_new_tokens=4)
    ref_eng.submit(ref)
    ref_eng.run_until_drained()

    eng = ServeEngine(cfg, params, n_slots=1, cache_len=48)
    other = Request(rid=1,
                    prompt=rng.integers(0, cfg.vocab, 12).astype(np.int32),
                    max_new_tokens=3)
    mine = Request(rid=2, prompt=prompt, max_new_tokens=4)
    eng.submit(other)
    eng.submit(mine)
    eng.run_until_drained()
    assert mine.output == ref.output


def test_engine_fused_act_backend_matches_ref():
    """Serving with the fused float->PPA->float kernel (one pallas_call per
    activation) produces exactly the greedy tokens of the unfused ref
    backend — the deployment hot path is bit-identical, just fused."""
    import dataclasses
    cfg, params = _setup()
    cfg = dataclasses.replace(cfg, act_impl="ppa", act_backend="ref")
    rng = np.random.default_rng(3)
    reqs = lambda: [Request(rid=i,
                            prompt=rng.integers(0, cfg.vocab, 8
                                                ).astype(np.int32),
                            max_new_tokens=4) for i in range(2)]
    rng = np.random.default_rng(3)
    a = reqs()
    ref_eng = ServeEngine(cfg, params, n_slots=2, cache_len=48)
    for r in a:
        ref_eng.submit(r)
    ref_eng.run_until_drained()
    assert ref_eng.cfg.act_backend == "ref"

    rng = np.random.default_rng(3)
    b = reqs()
    fused_eng = ServeEngine(cfg, params, n_slots=2, cache_len=48,
                            act_backend="pallas_fused")
    assert fused_eng.cfg.act_backend == "pallas_fused"
    for r in b:
        fused_eng.submit(r)
    fused_eng.run_until_drained()
    assert [r.output for r in b] == [r.output for r in a]


# ------------------------------------------------- coalesced bit-identity
def _run_both(cfg, params, lens, *, temps=None, n_slots=4, cache_len=48,
              max_new=4, seed=11):
    """Same request stream through a serial and a coalesced engine."""
    outs = []
    for coalesce in (False, True):
        reqs = _mixed_requests(cfg, lens, max_new=max_new, temps=temps,
                               seed=seed)
        eng = ServeEngine(cfg, params, n_slots=n_slots, cache_len=cache_len,
                          coalesce=coalesce)
        for r in reqs:
            eng.submit(r)
        eng.run_until_drained()
        assert all(r.done and len(r.output) == max_new for r in reqs)
        outs.append([r.output for r in reqs])
    return outs


def test_coalesced_matches_serial_greedy_mixed_lengths():
    """Micro-batched, length-bucketed admission emits exactly the tokens
    of per-request batch=1 admission — pads are invisible to real rows."""
    cfg, params = _setup()
    serial, coalesced = _run_both(cfg, params, [5, 8, 12, 16, 3, 9])
    assert coalesced == serial


def test_coalesced_matches_serial_temperature():
    """Fixed-seed temperature sampling is bit-identical: the coalesced
    path pre-splits keys in FIFO order and vmaps categorical, which must
    reproduce the per-slot split-then-sample stream exactly (greedy and
    temperature requests mixed)."""
    cfg, params = _setup()
    serial, coalesced = _run_both(cfg, params, [5, 8, 12, 8, 16, 6],
                                  temps=[0.0, 0.7, 1.3])
    assert coalesced == serial


@pytest.mark.parametrize("arch", ["rwkv6-3b", "hymba-1.5b"])
def test_coalesced_matches_serial_recurrent_arch(arch):
    """SSM/RWKV stages carry prompt-order state, so the engine must
    coalesce by exact length (batched, never padded) — and still match
    the serial engine token-for-token."""
    cfg, params = _setup(arch)
    eng = ServeEngine(cfg, params, n_slots=2, cache_len=48)
    assert not eng._paddable
    serial, coalesced = _run_both(cfg, params, [8, 8, 12, 8],
                                  temps=[0.0, 0.9], n_slots=2, max_new=3)
    assert coalesced == serial


def test_coalesced_matches_serial_ppa8_zoo():
    """The aggressive 8-bit NAF zoo serves the same tokens either way."""
    cfg, params = _setup()
    cfg8 = dataclasses.replace(cfg, act_impl="ppa8")
    serial, coalesced = _run_both(cfg8, params, [5, 12, 8, 7], max_new=3)
    assert coalesced == serial


def test_prefill_retraces_bounded_under_mixed_lengths():
    """Power-of-two length bucketing bounds distinct prefill shapes: many
    prompt lengths in [1, 16] through 2 slots trace at most
    (#buckets x #batch-sizes) prefill variants."""
    cfg, params = _setup()
    lens = [3, 5, 7, 9, 11, 13, 15, 16, 2, 6, 10, 14]
    reqs = _mixed_requests(cfg, lens, max_new=2)
    eng = ServeEngine(cfg, params, n_slots=2, cache_len=48)
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    # buckets hit: 8 and 16; batch sizes: 1 and 2
    assert eng.prefill_retraces <= 4
    assert eng.prefill_retraces == len(eng._prefill_shapes)


# ------------------------------------------------------ tenancy + pinning
def test_store_pin_exempts_from_lru():
    """Pinned entries neither count against max_entries nor get evicted;
    unpinning returns them to LRU life.  Uses the repo's committed table
    artifacts, so everything is a disk load — no compiles."""
    jobs = [CompileJob(naf=n, cfg=c, scheme=s)
            for n, c, s in ppa_table_jobs("ppa")]
    store = TableStore(max_entries=1)
    pinned = jobs[0]
    store.compile_or_load(pinned.naf, pinned.cfg, pinned.scheme)
    store.pin(pinned)
    for j in jobs[1:4]:
        store.compile_or_load(j.naf, j.cfg, j.scheme)
    assert store.compiles == 0          # artifacts served from disk
    # pinned entry survived three unpinned insertions through a cap of 1
    assert pinned.resolved().key() in store._mem
    assert store.stats()["in_memory"] == 2      # pinned + 1 LRU resident
    assert store.evictions == 2
    hits = store.hits_mem
    assert store.lookup(pinned) is not None
    assert store.hits_mem == hits + 1           # memory, not disk
    # unpin: the cap applies again and the ex-pinned entry can be evicted
    store.unpin(pinned)
    assert store.stats()["in_memory"] == 1
    j = jobs[4]
    store.compile_or_load(j.naf, j.cfg, j.scheme)
    assert pinned.resolved().key() not in store._mem


def test_tenant_front_warm_pin_fair_share():
    """Two tenants share one store: warm admission pins the NAF zoo,
    requests fair-share into the slot pool, outputs match a solo engine,
    and retiring a tenant unpins its tables."""
    cfg, params = _setup()
    cfg = dataclasses.replace(cfg, act_impl="ppa")
    store = TableStore(max_entries=2)
    front = TenantFront(store, max_active=4)
    rep = front.add_tenant(TenantSpec(
        name="a", cfg=cfg, params=params, n_slots=2, cache_len=48,
        warm_prompt_lens=(8,)))
    assert rep["tables_pinned"] == len(ppa_table_jobs(cfg.act_impl)) == 6
    assert rep["warm_traces"] == 2              # one prefill + one decode
    front.add_tenant(TenantSpec(name="b", cfg=cfg, params=params,
                                n_slots=2, cache_len=48))
    assert store.stats()["pinned"] == 6         # same zoo, same keys

    reqs_a = _mixed_requests(cfg, [8, 8, 8], max_new=3, seed=5)
    reqs_b = _mixed_requests(cfg, [8, 8, 8], max_new=3, seed=5)
    for ra, rb in zip(reqs_a, reqs_b):
        front.submit("a", ra)
        front.submit("b", rb)
    front.run_until_drained()
    assert all(r.done for r in reqs_a + reqs_b)
    # identical stream + identical engine seed -> identical tokens
    assert [r.output for r in reqs_a] == [r.output for r in reqs_b]

    # solo-engine reference for tenant a's stream
    ref = _mixed_requests(cfg, [8, 8, 8], max_new=3, seed=5)
    solo = ServeEngine(cfg, params, n_slots=2, cache_len=48)
    for r in ref:
        solo.submit(r)
    solo.run_until_drained()
    assert [r.output for r in reqs_a] == [r.output for r in ref]

    front.remove_tenant("b")
    assert store.stats()["pinned"] == 6         # ref-counted: a still pins
    front.remove_tenant("a")
    assert store.stats()["pinned"] == 0


def test_tenant_front_cold_is_lazy():
    """A cold tenant builds nothing until its first request is admitted."""
    cfg, params = _setup()
    front = TenantFront(TableStore())
    front.add_tenant(TenantSpec(name="cold", cfg=cfg, params=params,
                                n_slots=1, cache_len=48), warm=False)
    assert "cold" not in front.engines
    req = _mixed_requests(cfg, [8], max_new=2)[0]
    front.submit("cold", req)
    front.run_until_drained()
    assert req.done and len(req.output) == 2
    assert "cold" in front.engines
