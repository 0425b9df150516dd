"""Cross-backend parity: one DatapathPlan, one kernel body, every executor
bit-identical.

* every execution backend's integer datapath == the numpy golden model
  (``core.schemes.eval_table_int``) across the NAF zoo at both deployment
  precisions (16-bit FQA-O2 and 8-bit FQA-S4-O1);
* the full float deployment path (``ppa_apply``) and the gated path
  (``ppa_gate``) are float-bit-identical across every backend, including
  the fused float->PPA->float kernel;
* ``DatapathPlan`` reproduces the legacy inline shift derivations the
  kernels used to hand-roll (property test — hypothesis when installed,
  seeded random sweep otherwise);
* the shared body honors ``round_mults`` in every executor — regression
  for the softmax kernel that silently dropped the half-ULP add;
* both segment selects of the shared body (comparator sweep, blocked
  bisection) give the same bits on every zoo table, whichever one the
  table's segment count picks.
"""

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.compiler import compile_or_load
from repro.core import (DatapathPlan, FWLConfig, NAF_REGISTRY, PPAScheme,
                        eval_table_int, grid_for_interval)
from repro.kernels import (available_backends, body, get_backend, pack_table,
                           ppa_apply, ppa_eval_ref, ppa_gate, ppa_softmax,
                           register_backend, softmax_ppa_2d)

# deployment points: paper Table VI/VII conclusions (same as models layer)
CFG16 = FWLConfig(w_in=8, w_out=16, w_a=(8, 16), w_o=(16, 16), w_b=16)
CFG8 = FWLConfig(w_in=8, w_out=8, w_a=(8,), w_o=(8,), w_b=8)
SCHEME16 = PPAScheme(order=2, quantizer="fqa")
SCHEME8 = PPAScheme(order=1, m_shifters=4, quantizer="fqa")

ZOO = sorted(NAF_REGISTRY)
# executable on CPU: the pallas kernels interpret there (same body)
INT_BACKENDS = ["ref", "lut_value", "lut_index", "pallas"]
ALL_BACKENDS = INT_BACKENDS + ["pallas_fused"]

_TABLES = {}

# segmentation modes the parity sweeps run under: the kernel contract is
# layout-agnostic (explicit starts_int), so non-uniform tables must be as
# bit-identical across backends as the uniform ones
SEG_MODES = ["uniform", "nonuniform"]


def _table(naf: str, bits: int, seg: str = "uniform"):
    key = (naf, bits, seg)
    if key not in _TABLES:
        cfg, scheme = ((CFG16, SCHEME16) if bits == 16 else (CFG8, SCHEME8))
        if seg == "nonuniform":
            scheme = dataclasses.replace(scheme, segmenter="nonuniform")
        _TABLES[key] = compile_or_load(naf, cfg, scheme)
    return _TABLES[key]


# ---------------------------------------------------------------- int parity
@pytest.mark.parametrize("seg", SEG_MODES)
@pytest.mark.parametrize("bits", [16, 8])
@pytest.mark.parametrize("naf", ZOO)
def test_integer_datapath_parity(naf, bits, seg):
    """Every integer backend == eval_table_int, exactly, on the whole
    fixed-point input domain — for uniform- and non-uniform-searched
    tables alike."""
    tab = _table(naf, bits, seg)
    if seg == "nonuniform":
        assert tab.scheme.segmenter == "nonuniform"
        assert tab.scheme.tag.endswith("-NU")
    tc = pack_table(tab)
    grid = np.arange(tc.lo, tc.hi, dtype=np.int64)
    gold = eval_table_int(tab, grid)
    x = jnp.asarray(grid, jnp.int32)
    for be in INT_BACKENDS:
        got = np.asarray(get_backend(be).eval_int(tc, x), dtype=np.int64)
        np.testing.assert_array_equal(
            got, gold, err_msg=f"backend {be} diverges for {naf}@{bits}bit")


# -------------------------------------------------------------- float parity
@pytest.mark.parametrize("seg", SEG_MODES)
@pytest.mark.parametrize("bits", [16, 8])
@pytest.mark.parametrize("naf", ZOO)
def test_float_path_parity(naf, bits, seg):
    """ppa_apply is float-bit-identical across every backend (including the
    fused kernel) on in-interval, out-of-interval and negative inputs."""
    tab = _table(naf, bits, seg)
    tc = pack_table(tab)
    xs, xe = tc.interval
    rng = np.random.default_rng(hash((naf, bits)) & 0xFFFF)
    x = jnp.asarray(rng.uniform(xs - 0.5 - xe, xe + 0.5, size=(7, 153)),
                    jnp.float32)
    ref = np.asarray(ppa_apply(tc, x, backend="ref"))
    for be in ALL_BACKENDS[1:]:
        got = np.asarray(ppa_apply(tc, x, backend=be))
        np.testing.assert_array_equal(
            got, ref, err_msg=f"backend {be} diverges for {naf}@{bits}bit")


@pytest.mark.parametrize("seg", SEG_MODES)
@pytest.mark.parametrize("bits", [16, 8])
@pytest.mark.parametrize("naf", ["sigmoid_wide", "gelu_inner"])
def test_gated_path_parity(naf, bits, seg):
    """The gated op (silu = x*sigmoid(x), gelu = x*Phi(x)) is bit-identical
    whether the multiply runs inside the fused kernel or outside."""
    tc = pack_table(_table(naf, bits, seg))
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(0, 3, size=(5, 131)), jnp.float32)
    ref = np.asarray(ppa_gate(tc, x, backend="ref"))
    for be in ALL_BACKENDS[1:]:
        got = np.asarray(ppa_gate(tc, x, backend=be))
        np.testing.assert_array_equal(
            got, ref, err_msg=f"gated backend {be} diverges for {naf}")


# ----------------------------------------------------- segment select parity
@pytest.mark.parametrize("bits", [16, 8])
@pytest.mark.parametrize("naf", ZOO)
def test_segment_select_parity(naf, bits, monkeypatch):
    """The int kernel and the fused kernel, gated and ungated, give ``ref``'s
    bits on the blocked bisection, and the int and gated kernels on the
    comparator sweep too, at every start, every start - 1, the interval's
    ends and beyond, both signs — whichever select the table's segment
    count picks by default.  (The ungated kernel differs from the gated one
    only after the select; ``tests/test_kernels.py`` runs all three on both
    selects over synthetic tables, so it is left off the sweep here, where
    an interpreted 500-segment sweep costs seconds a call.)"""
    tc = pack_table(_table(naf, bits))
    st = np.asarray(tc.starts, np.int64)
    ints = np.concatenate([st, st - 1, [tc.lo, tc.hi - 1, tc.hi, tc.hi + 9]])
    ints = np.concatenate([ints, -ints])
    x_int = jnp.asarray(ints, jnp.int32)
    xf = jnp.asarray(ints / float(1 << tc.w_in), jnp.float32)
    runs = {
        "int": lambda be: get_backend(be).eval_int(tc, x_int),
        "fused": lambda be: ppa_apply(tc, xf, backend=be),
        "fused_gated": lambda be: ppa_gate(tc, xf, backend=be),
    }
    refs = {kernel: np.asarray(run("ref")) for kernel, run in runs.items()}
    for select, least in (("sweep", 1 << 30), ("bisect", 1)):
        monkeypatch.setattr(body, "BISECT_MIN_SEGMENTS", least)
        for kernel, run in runs.items():
            if select == "sweep" and kernel == "fused":
                continue
            be = "pallas" if kernel == "int" else "pallas_fused"
            np.testing.assert_array_equal(
                np.asarray(run(be)), refs[kernel],
                err_msg=f"{kernel} kernel on the {select}, {naf}@{bits}bit")


# -------------------------------------------------- plan vs legacy derivation
def _legacy_shift_constants(cfg: FWLConfig):
    """The inline derivation kernels/ppa.py and kernels/softmax_ppa.py used
    to hand-roll (pre-DatapathPlan), kept verbatim as the reference."""
    order = cfg.order
    shifts = [cfg.w_a[0] + cfg.w_in - cfg.w_o[0]]
    up_g, up_a = [], []
    cur = cfg.w_o[0]
    for i in range(1, order):
        wg = max(cur, cfg.w_a[i])
        up_g.append(wg - cur)
        up_a.append(wg - cfg.w_a[i])
        shifts.append(wg + cfg.w_in - cfg.w_o[i])
        cur = cfg.w_o[i]
    w_sum = max(cur, cfg.w_b)
    return (tuple(shifts), tuple(up_g), tuple(up_a), w_sum - cur,
            w_sum - cfg.w_b, w_sum - cfg.w_out, cur)


def _assert_plan_matches_legacy(cfg: FWLConfig):
    plan = DatapathPlan.from_config(cfg)
    shifts, up_g, up_a, up_h, up_b, down_out, w_pre_b = \
        _legacy_shift_constants(cfg)
    assert plan.mult_shifts == shifts
    assert plan.up_g == up_g and plan.up_a == up_a
    assert (plan.up_h, plan.up_b, plan.down_out) == (up_h, up_b, down_out)
    assert plan.w_pre_b == w_pre_b
    assert plan.order == cfg.order
    assert (plan.w_in, plan.w_out) == (cfg.w_in, cfg.w_out)
    # alignment shifts are always exact left shifts (never truncate)
    assert all(s >= 0 for s in plan.up_g + plan.up_a)
    assert plan.up_h >= 0 and plan.up_b >= 0


def _random_cfg(rng) -> FWLConfig:
    order = int(rng.integers(1, 4))
    return FWLConfig(
        w_in=int(rng.integers(1, 17)), w_out=int(rng.integers(1, 21)),
        w_a=tuple(int(rng.integers(1, 21)) for _ in range(order)),
        w_o=tuple(int(rng.integers(1, 21)) for _ in range(order)),
        w_b=int(rng.integers(1, 21)),
        round_mults=bool(rng.integers(0, 2)))


def test_plan_reproduces_legacy_derivation_sweep():
    """Seeded-random property sweep (always runs, hypothesis or not)."""
    rng = np.random.default_rng(0)
    for _ in range(500):
        _assert_plan_matches_legacy(_random_cfg(rng))


def test_plan_reproduces_legacy_derivation_hypothesis():
    pytest.importorskip("hypothesis", reason="hypothesis not installed")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    wl = st.integers(1, 20)

    @settings(max_examples=200, deadline=None)
    @given(st.integers(1, 16), wl, st.lists(wl, min_size=1, max_size=4),
           st.lists(wl, min_size=4, max_size=4), wl, st.booleans())
    def prop(w_in, w_out, w_a, w_o, w_b, rm):
        cfg = FWLConfig(w_in=w_in, w_out=w_out, w_a=tuple(w_a),
                        w_o=tuple(w_o[:len(w_a)]), w_b=w_b, round_mults=rm)
        _assert_plan_matches_legacy(cfg)

    prop()


# ------------------------------------------------------ round_mults parity
ROUND_CFG = FWLConfig(w_in=8, w_out=12, w_a=(8, 16), w_o=(16, 16), w_b=16,
                      round_mults=True)


def _round_table():
    # w_out=12 < w_b=16 forces down_out=4 > 0: the final output truncation
    # must stay a plain floor even when round_mults rounds the multiplier
    # outputs (a hand-rolled kernel copy once rounded it too).  mae_t is
    # relaxed to the 12-bit output ULP — the half-ULP default is unreachable
    # once down_out truncates four fractional bits.
    return compile_or_load("exp2_frac", ROUND_CFG, SCHEME16, mae_t=2.0 ** -12)


def test_round_mults_integer_parity_all_backends():
    """round_mults tables evaluate bit-identically on every backend —
    regression for the softmax kernel dropping the half-ULP add and for
    ref/pallas rounding the final down_out shift."""
    tab = _round_table()
    tc = pack_table(tab)
    assert tc.round_mults and tc.plan.round_mults
    assert tc.plan.down_out > 0
    grid = np.arange(tc.lo, tc.hi, dtype=np.int64)
    gold = eval_table_int(tab, grid)
    x = jnp.asarray(grid, jnp.int32)
    for be in INT_BACKENDS:
        got = np.asarray(get_backend(be).eval_int(tc, x), dtype=np.int64)
        np.testing.assert_array_equal(got, gold, err_msg=f"backend {be}")


def test_softmax_kernel_round_mults_regression():
    """The fused softmax kernel runs the shared body, so a round_mults exp2
    table produces the same result as the jnp wrapper (whose datapath is
    golden-verified above).  The old hand-rolled kernel copy ignored
    cfg.round_mults and diverged here."""
    tc = pack_table(_round_table())
    rng = np.random.default_rng(17)
    # no-padding shape: rows % block_m == 0, cols == 128, so every float
    # reduction sees identical shapes and the comparison is exact
    x = jnp.asarray(rng.normal(0, 4, size=(16, 128)), jnp.float32)
    y_k = np.asarray(softmax_ppa_2d(x, tc))
    y_w = np.asarray(ppa_softmax(tc, x))
    np.testing.assert_array_equal(y_k, y_w)


# ------------------------------------------------------------------ registry
def test_backend_registry_rejects_unknown():
    tc = pack_table(_table("sigmoid", 16))
    with pytest.raises(ValueError, match="unknown backend"):
        ppa_apply(tc, jnp.zeros((4,), jnp.float32), backend="nope")
    with pytest.raises(ValueError):
        register_backend("bad")          # neither hook given


def test_backend_registry_extension():
    """The documented "adding a backend" path: register an eval_int hook,
    get the full float conditioning (and gating) for free."""
    name = "_test_ref_clone"
    register_backend(
        name,
        eval_int=lambda tc, x: ppa_eval_ref(x, tc.starts, tc.coefs, tc.plan))
    try:
        assert name in available_backends()
        tc = pack_table(_table("sigmoid_wide", 16))
        x = jnp.asarray(np.linspace(-9, 9, 333), jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(ppa_gate(tc, x, backend=name)),
            np.asarray(ppa_gate(tc, x, backend="ref")))
    finally:
        from repro.kernels.ops import _BACKENDS
        _BACKENDS.pop(name, None)
