"""Analysis-layer tests: certifier soundness, certificate lifecycle, lint.

The soundness property (every concrete intermediate of the shared Horner
body lies inside its abstract interval) is exercised with hypothesis when
it is installed and with a seeded-random sweep otherwise, so the property
gate never silently disappears with the optional dependency.
"""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import (CERT_VERSION, Certificate, abstract_horner,
                            certify_config, certify_table, lint_paths,
                            node_fwls)
from repro.analysis.intervals import Interval, join_bounds, trace_horner
from repro.compiler.store import CompileJob, TableStore
from repro.core.datapath import FWLConfig
from repro.core.fixed_point import signed_bits
from repro.core.schemes import PPAScheme, PPATable

REPO_ROOT = Path(__file__).resolve().parents[1]

CFG7 = FWLConfig(w_in=7, w_out=7, w_a=(7,), w_o=(7,), w_b=7)
SCHEME7 = PPAScheme(order=1, m_shifters=None, quantizer="fqa_fast")
NU_SCHEME7 = dataclasses.replace(SCHEME7, segmenter="nonuniform")


def _random_cfg(rng):
    order = int(rng.integers(1, 3))
    return FWLConfig(
        w_in=int(rng.integers(4, 9)),
        w_out=int(rng.integers(4, 11)),
        w_a=tuple(int(rng.integers(4, 11)) for _ in range(order)),
        w_o=tuple(int(rng.integers(4, 11)) for _ in range(order)),
        w_b=int(rng.integers(4, 11)),
        round_mults=bool(rng.integers(0, 2)),
    )


def _random_interval(rng, width_bits):
    lo = int(rng.integers(-(1 << width_bits), (1 << width_bits)))
    hi = lo + int(rng.integers(0, 1 << width_bits))   # may be a point
    return Interval(lo, hi)


# --- fixed_point.signed_bits -------------------------------------------------

def test_signed_bits_minimal_widths():
    assert signed_bits(0, 0) == 1
    assert signed_bits(-1, 0) == 1
    assert signed_bits(0, 1) == 2
    assert signed_bits(-2, 1) == 2
    assert signed_bits(-128, 127) == 8
    assert signed_bits(-129, 0) == 9
    assert signed_bits(0, 128) == 9
    with pytest.raises(ValueError):
        signed_bits(1, 0)


# --- interval domain ---------------------------------------------------------

def test_interval_ops_sound_pointwise():
    """mul/add/shift of intervals contain the pointwise results."""
    rng = np.random.default_rng(7)
    for _ in range(300):
        a, b = _random_interval(rng, 10), _random_interval(rng, 10)
        sh = int(rng.integers(0, 6))
        xa = int(rng.integers(a.lo, a.hi + 1))
        xb = int(rng.integers(b.lo, b.hi + 1))
        assert (a + b).contains(xa + xb)
        assert (a * b).contains(xa * xb)
        assert (a >> sh).contains(xa >> sh)
        assert (a << sh).contains(xa << sh)


def test_interval_shift_rejects_negative_count():
    with pytest.raises(ValueError):
        Interval(0, 1) >> -1
    with pytest.raises(ValueError):
        Interval(0, 1) << -1


# --- certifier soundness: abstract contains concrete -------------------------

def _check_containment(cfg, rng, n_points=8):
    """One soundness example: random boxes, random concrete points."""
    a_iv = [_random_interval(rng, w + 1) for w in cfg.w_a]
    b_iv = _random_interval(rng, cfg.w_b + 1)
    x_iv = _random_interval(rng, cfg.w_in)
    bounds = abstract_horner(cfg, a_iv, b_iv, x_iv)
    assert set(bounds) == set(node_fwls(cfg))
    for _ in range(n_points):
        a = [int(rng.integers(iv.lo, iv.hi + 1)) for iv in a_iv]
        b = int(rng.integers(b_iv.lo, b_iv.hi + 1))
        x = int(rng.integers(x_iv.lo, x_iv.hi + 1))
        out, trace = trace_horner(cfg, a, b, x)
        assert trace["out"] == out
        for name, v in trace.items():
            nb = bounds[name]
            assert nb.lo <= v <= nb.hi, \
                f"{name}={v} escapes [{nb.lo}, {nb.hi}] for {cfg}"


def test_abstract_contains_trace_seeded_sweep():
    """Seeded-random soundness sweep: orders 1-2, both rounding modes,
    degenerate (point) intervals included by construction."""
    rng = np.random.default_rng(2026)
    for _ in range(150):
        _check_containment(_random_cfg(rng), rng)


def test_abstract_exact_on_point_intervals():
    """On all-point inputs the abstract run degenerates to the concrete
    trace: every bound is a single value (no over-approximation)."""
    rng = np.random.default_rng(11)
    for _ in range(50):
        cfg = _random_cfg(rng)
        a = [int(rng.integers(-(1 << w), 1 << w)) for w in cfg.w_a]
        b = int(rng.integers(-(1 << cfg.w_b), 1 << cfg.w_b))
        x = int(rng.integers(-(1 << cfg.w_in), 1 << cfg.w_in))
        bounds = abstract_horner(cfg, [Interval.point(v) for v in a],
                                 Interval.point(b), Interval.point(x))
        _, trace = trace_horner(cfg, a, b, x)
        for name, v in trace.items():
            assert bounds[name].lo == bounds[name].hi == v


try:
    from hypothesis import given, settings, strategies as st

    @st.composite
    def _hyp_case(draw):
        order = draw(st.integers(1, 2))
        cfg = FWLConfig(
            w_in=draw(st.integers(4, 8)), w_out=draw(st.integers(4, 10)),
            w_a=tuple(draw(st.integers(4, 10)) for _ in range(order)),
            w_o=tuple(draw(st.integers(4, 10)) for _ in range(order)),
            w_b=draw(st.integers(4, 10)),
            round_mults=draw(st.booleans()))
        seed = draw(st.integers(0, 2 ** 16))
        return cfg, seed

    @settings(max_examples=30, deadline=None)
    @given(case=_hyp_case())
    def test_abstract_contains_trace_hypothesis(case):
        cfg, seed = case
        _check_containment(cfg, np.random.default_rng(seed))
except ImportError:      # seeded sweep above carries the property gate
    pass


# --- table certification -----------------------------------------------------

@pytest.fixture(scope="module")
def sigmoid_table(tmp_path_factory):
    store = TableStore(tmp_path_factory.mktemp("certstore"))
    return store.compile_or_load("sigmoid", CFG7, SCHEME7)


def test_certify_table_proves_smoke_config(sigmoid_table):
    cert = certify_table(sigmoid_table)
    assert cert.ok and not cert.violations
    assert cert.mode == "table" and cert.carrier_bits == 32
    names = {n["name"] for n in cert.nodes}
    assert {"p1", "h1", "sum", "out"} <= names
    assert cert.max_bits <= 32


def _assert_full_grid_containment(tab):
    """Full-grid containment: the per-table certificate bounds hold for
    every representable input, per the table's own segment selection."""
    cfg = tab.cfg
    lo = int(np.ceil(tab.interval[0] * (1 << cfg.w_in) - 1e-12))
    hi = int(np.ceil(tab.interval[1] * (1 << cfg.w_in) - 1e-12))
    cert = certify_table(tab)
    joined = {n["name"]: n for n in cert.nodes}
    for x in range(lo, hi):
        s = int(np.clip(np.searchsorted(tab.starts_int, x, side="right") - 1,
                        0, tab.num_segments - 1))
        _, trace = trace_horner(cfg, [int(v) for v in tab.a_int[s]],
                                int(tab.b_int[s]), x)
        for name, v in trace.items():
            assert joined[name]["lo"] <= v <= joined[name]["hi"]


def test_certified_bounds_contain_every_grid_point(sigmoid_table):
    _assert_full_grid_containment(sigmoid_table)


def test_certify_config_envelope_records_assumptions():
    cert = certify_config("sigmoid", CFG7, SCHEME7)
    assert cert.mode == "envelope"
    assert cert.assumptions           # estimate, not proof — says so
    assert cert.ok                    # 7-bit widths sit far inside int32


def test_certificate_json_roundtrip(sigmoid_table):
    cert = certify_table(sigmoid_table)
    cert.meta = {"v": CompileJob.VERSION, "key": "abc"}
    again = Certificate.from_json(cert.to_json())
    assert again.to_json() == cert.to_json()
    assert again.cert_version == CERT_VERSION


def test_join_bounds_is_hull():
    nb = abstract_horner(CFG7, [Interval(-3, 5)], Interval(-7, 7),
                         Interval(0, 10))
    nb2 = abstract_horner(CFG7, [Interval(-9, 2)], Interval(-1, 1),
                          Interval(-10, 0))
    j = join_bounds([nb, nb2])
    for name in nb:
        assert j[name].lo == min(nb[name].lo, nb2[name].lo)
        assert j[name].hi == max(nb[name].hi, nb2[name].hi)


# --- non-uniform tables: certificate soundness + lifecycle -------------------

@pytest.fixture(scope="module")
def sigmoid_nu_table(tmp_path_factory):
    store = TableStore(tmp_path_factory.mktemp("nucertstore"))
    return store.compile_or_load("sigmoid", CFG7, NU_SCHEME7)


def test_certify_nonuniform_table_proves_overflow_freedom(sigmoid_nu_table):
    tab = sigmoid_nu_table
    assert tab.scheme.segmenter == "nonuniform"
    cert = certify_table(tab)
    assert cert.ok and not cert.violations
    assert cert.mode == "table" and cert.max_bits <= 32


def test_certified_bounds_contain_every_grid_point_nonuniform(
        sigmoid_nu_table):
    """The certifier joins per-segment boxes over the table's *actual*
    breakpoints, so the proof stays sound under non-uniform layouts."""
    _assert_full_grid_containment(sigmoid_nu_table)


def test_cert_retired_when_segmentation_mode_changes(tmp_path):
    """Uniform and non-uniform certificates live under distinct keys; a
    certificate stamped for one segmentation mode never serves the other —
    the stale-stamp retirement fires on first serve."""
    store = TableStore(tmp_path)
    job_u = CompileJob("sigmoid", CFG7, SCHEME7)
    job_n = CompileJob("sigmoid", CFG7, NU_SCHEME7)
    assert job_u.key() != job_n.key()
    assert store.cert_path(job_u) != store.cert_path(job_n)
    store.certify(job_u)
    store.compile_or_load(job_n.naf, job_n.cfg, job_n.scheme)
    # emulate a segmentation-mode mixup: the uniform certificate lands in
    # the non-uniform certificate slot (its key stamp cannot match)
    path_n = store.cert_path(job_n)
    path_n.write_text(store.cert_path(job_u).read_text())
    fresh = TableStore(tmp_path)          # new process's view of the dir
    assert fresh.load_certificate(job_n) is None
    fresh.compile_or_load(job_n.naf, job_n.cfg, job_n.scheme)
    assert not path_n.exists()            # retired on first serve
    assert fresh.stats()["certs_stale"] >= 1
    assert store.cert_path(job_u).exists()   # the honest one survives
    # re-certifying under the right key makes the certificate loadable
    cert = fresh.certify(job_n)
    assert cert.ok
    assert fresh.load_certificate(job_n) is not None


# --- store lifecycle ---------------------------------------------------------

def test_store_certify_roundtrip(tmp_path):
    store = TableStore(tmp_path)
    job = CompileJob("sigmoid", CFG7, SCHEME7)
    cert = store.certify(job)
    assert cert.ok
    assert store.cert_path(job).exists()
    loaded = store.load_certificate(job)
    assert loaded is not None
    assert loaded.to_json() == cert.to_json()


def test_store_retires_stale_certificate(tmp_path):
    store = TableStore(tmp_path)
    job = CompileJob("sigmoid", CFG7, SCHEME7)
    store.certify(job)
    # corrupt the stamp the way a compiler-version bump would
    path = store.cert_path(job)
    blob = json.loads(path.read_text())
    blob["meta"]["v"] = CompileJob.VERSION - 1
    path.write_text(json.dumps(blob))

    fresh = TableStore(tmp_path)          # new process's view of the dir
    assert fresh.load_certificate(job) is None
    fresh.compile_or_load(job.naf, job.cfg, job.scheme)
    assert not path.exists()              # retired on first serve
    st = fresh.stats()
    assert st["certs_checked"] == 1 and st["certs_stale"] == 1


def test_store_keeps_fresh_certificate(tmp_path):
    store = TableStore(tmp_path)
    job = CompileJob("sigmoid", CFG7, SCHEME7)
    store.certify(job)
    fresh = TableStore(tmp_path)
    fresh.compile_or_load(job.naf, job.cfg, job.scheme)
    assert store.cert_path(job).exists()
    st = fresh.stats()
    assert st["certs_checked"] == 1 and st["certs_stale"] == 0


def test_prune_removes_companion_certificates(tmp_path):
    store = TableStore(tmp_path)
    job = CompileJob("sigmoid", CFG7, SCHEME7)
    store.certify(job)
    assert store.cert_path(job).exists()
    store.prune(max_files=0)
    assert not store.cert_path(job).exists()


# --- kernel pack guard -------------------------------------------------------

def test_pack_table_rejects_overflowing_table():
    from repro.kernels.ops import pack_table

    cfg = FWLConfig(w_in=15, w_out=8, w_a=(20,), w_o=(8,), w_b=8)
    tab = PPATable(
        naf="sigmoid", interval=(0.0, 1.0), cfg=cfg,
        scheme=PPAScheme(order=1),
        starts_int=np.array([0], dtype=np.int64),
        a_int=np.array([[1 << 19]], dtype=np.int64),
        b_int=np.array([0], dtype=np.int64),
        mae_hard=0.0, mae_t=1.0)
    with pytest.raises(ValueError, match="overflows the int32 datapath"):
        pack_table(tab)


# --- lint --------------------------------------------------------------------

def _lint_fixture(tmp_path, rel, body):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(body)
    return p


def test_lint_host_sync_fires_and_suppresses(tmp_path):
    body = (
        "import jax.numpy as jnp\n"
        "def _sample(x):\n"
        "    y = jnp.argmax(x)\n"
        "    return int(y)\n"
    )
    p = _lint_fixture(tmp_path, "serve/engine.py", body)
    found = lint_paths([p])
    assert [f.rule for f in found] == ["host-sync"]

    suppressed = body.replace(
        "    return int(y)",
        "    # analysis: allow(host-sync)\n    return int(y)")
    p.write_text(suppressed)
    assert lint_paths([p]) == []


def test_lint_taint_boundary_host_call_launders(tmp_path):
    """A host helper fed a device value returns a host value: indexing or
    int() on its result must NOT be flagged (the seed false positive)."""
    body = (
        "import jax.numpy as jnp\n"
        "def _to_host(v):\n"
        "    return v\n"
        "def _sample(self, x):\n"
        "    rows = _to_host(jnp.argmax(x))\n"
        "    return [int(rows[0])]\n"
    )
    p = _lint_fixture(tmp_path, "serve/engine.py", body)
    assert lint_paths([p]) == []


def test_lint_float_contamination_in_golden_path(tmp_path):
    body = (
        "def horner_int(sel, x, plan):\n"
        "    return sel[0] * x / 2\n"
    )
    p = _lint_fixture(tmp_path, "kernels/helper.py", body)
    found = lint_paths([p])
    assert [f.rule for f in found] == ["float-int-path"]


@pytest.mark.parametrize("fn", ["select_coeffs_sweep",
                                "select_coeffs_bisect"])
def test_lint_segment_selects_are_integer_only(fn, tmp_path):
    """Both segment selects are under the integer golden-path contract: a
    float step in either is flagged, and the shipped body is clean."""
    from repro.analysis.lint import GOLDEN_PATH_FUNCTIONS
    assert fn in GOLDEN_PATH_FUNCTIONS
    body = (
        "import jax.numpy as jnp\n"
        f"def {fn}(x_int, starts_ref, coef_ref, *, num_segments, order):\n"
        "    lane = jnp.zeros(x_int.shape, jnp.int32)\n"
        "    return [lane + num_segments / 2]\n"
    )
    p = _lint_fixture(tmp_path, "kernels/body.py", body)
    assert [f.rule for f in lint_paths([p])] == ["float-int-path"]
    p.write_text(body.replace("num_segments / 2", "num_segments // 2"))
    assert lint_paths([p]) == []
    assert lint_paths([REPO_ROOT / "src/repro/kernels/body.py"]) == []


def test_lint_tracer_branch_in_traced_file(tmp_path):
    body = (
        "import jax.numpy as jnp\n"
        "def f(x):\n"
        "    if jnp.max(x) > 0:\n"
        "        return x\n"
        "    return -x\n"
    )
    p = _lint_fixture(tmp_path, "kernels/ref.py", body)
    found = lint_paths([p])
    assert [f.rule for f in found] == ["tracer-branch"]


def test_lint_nondet_iteration_near_keys(tmp_path):
    body = (
        "import glob\n"
        "def merge(root):\n"
        "    out = []\n"
        "    for f in glob.glob(root):\n"
        "        out.append(f)\n"
        "    return out\n"
    )
    p = _lint_fixture(tmp_path, "compiler/store.py", body)
    found = lint_paths([p])
    assert [f.rule for f in found] == ["nondet-iter"]
    # sorted() around the glob is the fix, and satisfies the rule
    p.write_text(body.replace("glob.glob(root)", "sorted(glob.glob(root))"))
    assert lint_paths([p]) == []


def test_repo_lint_gate_is_clean():
    """The CI gate scope lints clean — every deliberate exception carries
    an inline justification, so new findings are always actionable."""
    found = lint_paths(root=REPO_ROOT)
    assert found == [], "\n".join(f.describe() for f in found)


def test_jaxpr_golden_path_stays_integer():
    pytest.importorskip("jax")
    from repro.analysis.lint import jaxpr_golden_check
    assert jaxpr_golden_check() == []
