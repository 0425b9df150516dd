"""What a profiler trace of the serving engine holds: the ``serve.*`` host
spans of ``ServeEngine.step()`` with their arguments, the ``act.*`` scopes
of the activation bundle in the program's op metadata, and the named
``serve_prefill`` / ``serve_decode`` programs, which differ from unnamed,
unscoped ones in metadata only."""

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler import default_store
from repro.configs import get_smoke_config
from repro.models import ShardCtx, decode_step, init_params, param_specs
from repro.models import activations
from repro.serve import Request, ServeEngine
from repro.serve import engine as engine_mod

STEP = "test.step"          # the caller's own span around each step()


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("internlm2-1.8b")
    params = init_params(param_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg, lens, base=0, max_new=3):
    rng = np.random.default_rng(5)
    return [Request(rid=base + i,
                    prompt=rng.integers(0, cfg.vocab, n).astype(np.int32),
                    max_new_tokens=max_new)
            for i, n in enumerate(lens)]


def _host_spans(logdir):
    """(name, t0, t1, args) of every host event named ``serve.*`` or
    after the caller's and the harness's spans."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve.") or ev.name in (
                        STEP, "engine.step"):
                    t0 = int(ev.start_ns)
                    out.append((ev.name, t0, t0 + int(ev.duration_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _ints(v):
    return [int(x) for x in re.findall(r"-?\d+", str(v))]


@pytest.fixture(scope="module")
def traced(setup, tmp_path_factory):
    """Three prompts of two buckets through a 4-slot engine, each step()
    traced inside the caller's span."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, n_slots=4, cache_len=48)
    for r in _requests(cfg, [5, 7, 12], base=100):       # compile untraced
        eng.submit(r)
    eng.run_until_drained()
    reqs = _requests(cfg, [5, 7, 12])
    for r in reqs:
        eng.submit(r)
    logdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(logdir)
    n_steps = 0
    while eng.queue or any(r is not None for r in eng.slot_req):
        with jax.profiler.TraceAnnotation(STEP):
            eng.step()
        n_steps += 1
    jax.profiler.stop_trace()
    return reqs, n_steps, _host_spans(logdir)


def test_span_names_and_nesting(traced):
    reqs, n_steps, spans = traced
    names = {n for n, *_ in spans}
    assert "engine.step" not in names          # the harness owns it
    assert names == {STEP, "serve.admit", "serve.prefill",
                     "serve.insert_cache", "serve.decode", "serve.sync",
                     "serve.bookkeep"}
    steps = [s for s in spans if s[0] == STEP]
    # the first token comes from the prefill, two decode steps follow
    assert len(steps) == n_steps == 2
    for name, a, b, _ in spans:
        if name == STEP:
            continue
        # every serve.* span lies inside exactly one step() call
        assert sum(s[1] <= a and b <= s[2] for s in steps) == 1, name
    admits = [s for s in spans if s[0] == "serve.admit"]
    for name, a, b, args in spans:
        inside = any(s[1] <= a and b <= s[2] for s in admits)
        if name in ("serve.prefill", "serve.insert_cache"):
            assert inside, name
        if name == "serve.sync":
            assert inside == (args["phase"] == 0)
        if name in ("serve.decode", "serve.bookkeep"):
            assert not inside, name
    # one step() writes admit, decode, sync, bookkeep; the first one also
    # a prefill, a sync and an insert per group
    per_step = [sum(s[1] <= a and b <= s[2] for _, a, b, _ in spans) - 1
                for s in steps]
    assert per_step == [4 + 3 * 2, 4]


def test_span_arguments(traced):
    reqs, _, spans = traced
    by = {}
    for name, _, _, args in spans:
        by.setdefault(name, []).append(args)
    first = by["serve.admit"][0]
    assert (first["rows"], first["groups"]) == (3, 2)
    assert all((a["rows"], a["groups"]) == (0, 0)
               for a in by["serve.admit"][1:])
    pre = by["serve.prefill"]
    # buckets 8 (prompts 5, 7) and 16 (prompt 12)
    assert sorted((a["bucket"], a["rows"]) for a in pre) == [(8, 2), (16, 1)]
    for a in pre:
        rids = _ints(a["rids"])
        assert len(rids) == a["rows"]
        assert a["real_tokens"] == sum(len(reqs[r].prompt) for r in rids)
        assert a["padded_tokens"] == a["bucket"] * a["rows"]
    assert sorted(r for a in pre for r in _ints(a["rids"])) == [0, 1, 2]
    assert sorted(a["rows"] for a in by["serve.insert_cache"]) == [1, 2]
    assert [a["active"] for a in by["serve.decode"]] == [3, 3]
    syncs = sorted((a["phase"], a["rows"]) for a in by["serve.sync"])
    assert syncs == [(0, 1), (0, 2), (1, 3), (1, 3)]
    assert [a["finished"] for a in by["serve.bookkeep"]] == [0, 3]


def test_serial_admission_spans(setup, tmp_path):
    """The batch=1 admission path writes the same spans, one prefill of
    one unpadded row per request."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, n_slots=2, cache_len=48, coalesce=False)
    reqs = _requests(cfg, [5, 9], max_new=2)
    for r in reqs:
        eng.submit(r)
    jax.profiler.start_trace(str(tmp_path))
    eng.run_until_drained()
    jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    pre = [args for n, _, _, args in spans if n == "serve.prefill"]
    assert [(a["bucket"], a["rows"], a["real_tokens"], a["padded_tokens"],
             _ints(a["rids"])) for a in pre] == [(5, 1, 5, 5, [0]),
                                                 (9, 1, 9, 9, [1])]
    assert [a["phase"] for n, _, _, a in spans if n == "serve.sync"] \
        == [0, 0, 1]


def test_reap_span_only_with_deadlines(setup, tmp_path):
    cfg, params = setup
    eng = ServeEngine(cfg, params, n_slots=2, cache_len=48)
    r = _requests(cfg, [5], max_new=2)[0]
    r.deadline_s = 0.0                       # expired before admission
    eng.submit(r)
    jax.profiler.start_trace(str(tmp_path))
    eng.step()
    jax.profiler.stop_trace()
    reaps = [a for n, _, _, a in _host_spans(str(tmp_path))
             if n == "serve.reap"]
    assert [a["n"] for a in reaps] == [1] and r.timed_out



def test_admit_override_keeps_working(setup, monkeypatch):
    """``chip_smoke``'s recording engine overrides ``_admit`` and calls the
    base method for its side effects: stepping it must still serve every
    request and sample 3 prefill and 7 decode logits batches."""
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), os.pardir, "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    monkeypatch.setattr(smoke, "peak_bytes", lambda device: 0)
    cfg, params = setup
    outputs, seen = smoke.serve(cfg, params, None, "ref",
                                smoke.prompts_for(cfg.vocab))
    assert [len(o) for o in outputs] == [smoke.MAX_NEW] * 4
    phases = [p for p, _ in seen]
    assert phases == ["prefill"] * 3 + ["decode"] * (smoke.MAX_NEW - 1)

# ------------------------------------------------------ scopes and names
def _engine(setup, impl, monkeypatch=None, raw=False):
    cfg, params = setup
    cfg = dataclasses.replace(cfg, act_impl=impl)
    if raw:   # the bundle as built, without its act.* scopes
        bundle = (activations._exact_bundle() if impl == "exact" else
                  activations._ppa_bundle(16, cfg.act_backend,
                                          default_store()))
        monkeypatch.setattr(engine_mod, "make_model_acts",
                            lambda cfg, store=None: bundle)
    return ServeEngine(cfg, params, n_slots=2, cache_len=32)


def _lower_decode(eng, fn=None):
    fn = eng._decode if fn is None else fn
    return fn.lower(eng.params, eng.cache, jnp.zeros((2, 1), jnp.int32),
                    jnp.zeros((2,), jnp.int32))


def _lower_prefill(eng):
    return eng._prefill.lower(eng.params,
                              {"tokens": jnp.zeros((2, 8), jnp.int32)},
                              jnp.full((2,), 7, jnp.int32))


@pytest.mark.parametrize("impl", ["exact", "ppa"])
def test_act_scopes_in_program_metadata(setup, impl):
    eng = _engine(setup, impl)
    for name, low in (("serve_decode", _lower_decode(eng)),
                      ("serve_prefill", _lower_prefill(eng))):
        assert f"module @jit_{name} " in low.as_text()
        paths = set(re.findall(r'loc\("([^"]*act\.[a-z_]+)',
                               low.as_text(debug_info=True)))
        scopes = {re.search(r"act\.[a-z_]+", p).group() for p in paths}
        # the smoke model's MLP gate and its attention softmax
        assert scopes == {"act.silu", "act.softmax"}, (name, paths)


def _strip(text: str) -> str:
    """HLO text without what only names things: metadata, the module's
    name, stack frames and instruction numbers."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    text = re.sub(r", stack_frame_id=\d+", "", text)
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames"
                  r"|\d+ .*)\n", "", text, flags=re.M)
    text = re.sub(r"^(HloModule |module @)[\w.]+", r"\1m", text, flags=re.M)
    return re.sub(r"\.\d+\b", ".N", text)


@pytest.mark.parametrize("impl", ["exact", "ppa"])
def test_names_and_scopes_change_metadata_only(setup, impl, monkeypatch):
    """``serve_decode`` with act.* scopes against the unnamed, unscoped
    decode program the engine ran before: the same program once names
    and metadata are stripped, before and after compilation."""
    cfg, _ = setup
    eng = _engine(setup, impl)
    raw_eng = _engine(setup, impl, monkeypatch, raw=True)
    acts, ctx = raw_eng.acts, ShardCtx()
    before = jax.jit(lambda p, c, t, pos: decode_step(
        p, raw_eng.cfg, c, t, pos, acts, ctx))
    new, old = _lower_decode(eng), _lower_decode(raw_eng, before)
    assert "act." in new.as_text(debug_info=True)
    assert "act." not in old.as_text(debug_info=True)
    assert _strip(new.as_text()) == _strip(old.as_text())
    compiled = new.compile().as_text()
    # the op_name an op of the compiled program carries, as a trace sees it
    assert re.search(r'op_name="jit\(serve_decode\)/[^"]*/act\.silu/',
                     compiled)
    assert _strip(compiled) == _strip(old.compile().as_text())


def test_scoped_bundle_wraps_every_callable():
    raw, scoped = activations._exact_bundle(), activations.make_acts("exact")
    x = jnp.linspace(-4.0, 4.0, 33)
    for f in dataclasses.fields(raw):
        if f.name == "impl":
            assert scoped.impl == raw.impl
            continue
        got, want = getattr(scoped, f.name), getattr(raw, f.name)
        assert got is not want
        np.testing.assert_array_equal(np.asarray(got(x)),
                                      np.asarray(want(x)))
        text = jax.jit(got).lower(x).as_text(debug_info=True)
        assert f"act.{f.name}" in text
