"""The traffic generator: seeded, deterministic, in bounds, the same work
for every seed, and a warm-up that covers every shape a mix can cause."""

import itertools
import json
from pathlib import Path

import numpy as np
import pytest

from fqabench import harness
from fqabench.traffic import Mix, check_sample

BENCH = Path(__file__).resolve().parents[1]
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))
SEEDS = (0, 7, 2**31 + 12345)


def mix(name):
    return harness.load_mix(BENCH, name)


def jobs(m: Mix, seed: int, n: int = 200):
    if m.kind == "open_loop":
        return m.open_loop(seed, 40.0, 1000)
    return list(itertools.islice(m.stream(seed, 1000), n))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a, b = jobs(mix(name), 3), jobs(mix(name), 3)
    assert [(j.due_s, j.max_new_tokens) for j in a] == \
        [(j.due_s, j.max_new_tokens) for j in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = jobs(mix(name), 4)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_bounds_and_same_work_for_every_seed(name):
    m = mix(name)
    sizes = []
    for seed in SEEDS:
        js = jobs(m, seed, n=2 * int(m.spec.get("block", 1)))
        p = [len(j.prompt) for j in js]
        o = [j.max_new_tokens for j in js]
        assert min(p) >= m.spec["prompt"]["min"]
        assert max(p) <= m.spec["prompt"]["max"]
        assert min(o) >= m.spec["output"]["min"]
        assert max(o) <= m.spec["output"]["max"]
        assert all(0 <= j.prompt.min() and j.prompt.max() < 1000 for j in js)
        sizes.append((sorted(p), sorted(o)))
    assert all(s == sizes[0] for s in sizes)


def test_open_loop_arrivals():
    m = mix("chat")
    seconds = 40.0
    per_seed = []
    for seed in SEEDS:
        due = [j.due_s for j in m.open_loop(seed, seconds, 1000)]
        assert due[0] == 0.0 and due == sorted(due)
        assert due[-1] < seconds
        assert len(due) == round(m.spec["rate_per_s"] * seconds)
        # the gaps, with the last one closing the window, sum to it
        per_seed.append(sorted(np.diff(due + [seconds]).round(9)))
    assert all(g == per_seed[0] for g in per_seed)


@pytest.mark.parametrize("name", MIXES)
def test_warm_shapes_cover_the_mix(name):
    """Every (bucket, admission group size) the mix can cause, under the
    engine's own bucketing, is in the warm-up set."""
    from repro.configs import get_smoke_config
    from repro.models import init_params, param_specs
    from repro.serve import ServeEngine
    import jax

    m = mix(name)
    cfg = get_smoke_config("internlm2-1.8b")
    eng = ServeEngine(cfg, init_params(param_specs(cfg),
                                       jax.random.PRNGKey(0)),
                      n_slots=m.n_slots, cache_len=m.cache_len)
    warm = {(b, g) for b, _, g in m.warm_shapes(eng._bucket_len)}
    for n in m.prompt_range:
        for g in range(1, m.n_slots + 1):
            assert (eng._bucket_len(n), g) in warm
    for b, plen, _ in m.warm_shapes(eng._bucket_len):
        assert eng._bucket_len(plen) == b and plen in m.prompt_range


def test_check_sample_keeps_the_longest():
    class R:
        def __init__(self, rid, n):
            self.rid, self.output = rid, [0] * n
    rs = [R(i, n) for i, n in enumerate([5, 9, 3, 40, 7, 7, 2, 11, 6, 1])]
    a, b = check_sample(rs, 4, 11), check_sample(rs, 4, 11)
    assert [r.rid for r in a] == [r.rid for r in b]
    assert a[0].rid == 3 and len(a) == 4 and len({r.rid for r in a}) == 4
    assert check_sample(rs[:2], 4, 11)[0].rid == 1


def test_mix_files_are_complete():
    for name in MIXES:
        spec = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
        m = Mix(name, spec)
        assert m.spec["prompt"]["max"] + m.spec["output"]["max"] \
            <= m.cache_len
