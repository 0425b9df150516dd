"""Kernel-layer equivalence: numpy golden == jnp ref == Pallas kernel.

Per the deliverable spec: sweep shapes/dtypes for each Pallas kernel and
assert_allclose (here: exact integer equality where the datapath is integer,
allclose for the float softmax wrapper) against the ref.py oracle.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (FWLConfig, PPAScheme, eval_table_int,
                        grid_for_interval, get_table)
from repro.kernels import (body, get_backend, pack_table, ppa_act, ppa_apply,
                           ppa_eval_2d, ppa_eval_ref, ppa_fused_apply,
                           ppa_gate, ppa_softmax, softmax_ppa_2d)

CFG8 = FWLConfig(w_in=8, w_out=8, w_a=(8,), w_o=(8,), w_b=8)
CFG16 = FWLConfig(w_in=8, w_out=16, w_a=(8, 16), w_o=(16, 16), w_b=16)


@pytest.fixture(scope="module")
def tab8():
    return get_table("sigmoid", CFG8, PPAScheme(order=1, quantizer="fqa"))


@pytest.fixture(scope="module")
def tab16():
    return get_table("sigmoid", CFG16, PPAScheme(order=2, quantizer="fqa"))


@pytest.fixture(scope="module")
def tab_exp2():
    return get_table("exp2_frac", CFG16, PPAScheme(order=2, quantizer="fqa"))


# ---------------------------------------------------------------- int paths
@pytest.mark.parametrize("shape", [(8, 128), (16, 256), (256, 128), (24, 384)])
@pytest.mark.parametrize("which", ["tab8", "tab16"])
def test_pallas_matches_ref_and_golden(which, shape, request):
    tab = request.getfixturevalue(which)
    tc = pack_table(tab)
    rng = np.random.default_rng(0)
    lo, hi = int(tab.starts_int[0]), int((1 << tab.cfg.w_in)) - 1
    x = rng.integers(lo, hi + 1, size=shape).astype(np.int32)

    y_ref = np.asarray(ppa_eval_ref(jnp.asarray(x), tc.starts, tc.coefs,
                                    tc.plan))
    bm = shape[0] if shape[0] in (8, 16, 24, 256) else 8
    y_pal = np.asarray(ppa_eval_2d(jnp.asarray(x), tc.starts, tc.coefs,
                                   tc.plan, block=(min(bm, 8), 128)))
    y_gold = eval_table_int(tab, x.astype(np.int64))
    np.testing.assert_array_equal(y_ref, y_gold)
    np.testing.assert_array_equal(y_pal, y_gold)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([(1, 7), (3, 130), (130,)]))
def test_ref_matches_golden_random_shapes(seed, shape):
    tab = get_table("sigmoid", CFG8, PPAScheme(order=1, quantizer="fqa"))
    tc = pack_table(tab)
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 1 << tab.cfg.w_in, size=shape).astype(np.int32)
    y_ref = np.asarray(ppa_eval_ref(jnp.asarray(x), tc.starts, tc.coefs,
                                    tc.plan))
    np.testing.assert_array_equal(y_ref, eval_table_int(tab, x))


def test_pallas_backend_through_ppa_apply(tab8):
    """The padded/reshaped pallas path in ops.py is exact vs ref backend."""
    tc = pack_table(tab8)
    rng = np.random.default_rng(3)
    for shape in [(5,), (3, 100), (2, 3, 50)]:
        x = jnp.asarray(rng.uniform(-4, 4, size=shape), dtype=jnp.float32)
        a = ppa_apply(tc, x, backend="ref")
        b = ppa_apply(tc, x, backend="pallas")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("backend",
                         ["lut_index", "lut_value", "pallas_fused"])
@pytest.mark.parametrize("which", ["tab8", "tab16"])
def test_lut_backends_bit_exact(which, backend, request):
    """The beyond-paper LUT/fused deployment modes match the datapath
    exactly."""
    tab = request.getfixturevalue(which)
    tc = pack_table(tab)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(0, 2, (777,)), jnp.float32)
    a = ppa_apply(tc, x, backend="ref")
    b = ppa_apply(tc, x, backend=backend)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- float wrapper
def test_ppa_apply_mae_bound(tab8):
    """End-to-end float path respects the table's MAE on the fitted interval."""
    tc = pack_table(tab8)
    x_int = grid_for_interval(0.0, 1.0, 8)
    x = jnp.asarray(x_int / 256.0, dtype=jnp.float32)
    y = np.asarray(ppa_apply(tc, x))
    f = 1.0 / (1.0 + np.exp(-np.asarray(x, dtype=np.float64)))
    assert np.abs(f - y).max() <= tab8.mae_hard + 1e-7


def test_ppa_apply_symmetry(tab8):
    """sigmoid(-x) == 1 - sigmoid(x) bit-exactly through the table."""
    tc = pack_table(tab8)
    x = jnp.asarray(np.linspace(0.01, 0.99, 64), dtype=jnp.float32)
    y_pos = np.asarray(ppa_apply(tc, x), dtype=np.float64)
    y_neg = np.asarray(ppa_apply(tc, -x), dtype=np.float64)
    np.testing.assert_allclose(y_neg, 1.0 - y_pos, atol=1e-6)


def test_ppa_apply_saturation():
    tab = get_table("sigmoid_wide", CFG16, PPAScheme(order=2, quantizer="fqa"))
    tc = pack_table(tab)
    x = jnp.asarray([9.0, 20.0, 100.0, -9.0, -100.0], dtype=jnp.float32)
    y = np.asarray(ppa_apply(tc, x))
    np.testing.assert_allclose(y[:3], 1.0, atol=1e-6)
    np.testing.assert_allclose(y[3:], 0.0, atol=1e-6)


def test_minus_x_symmetry_softplus():
    tab = get_table("softplus", CFG16, PPAScheme(order=2, quantizer="fqa"))
    tc = pack_table(tab)
    x = jnp.asarray(np.linspace(-7.5, 7.5, 101), dtype=jnp.float32)
    y = np.asarray(ppa_apply(tc, x), dtype=np.float64)
    f = np.log1p(np.exp(-np.abs(np.asarray(x, np.float64)))) + np.maximum(
        np.asarray(x, np.float64), 0)
    assert np.abs(y - f).max() < 2e-3  # table MAE + sym reconstruction


def test_ppa_act_gradient(tab8):
    """Straight-through backward equals the exact sigmoid derivative."""
    tc = pack_table(tab8)
    x = jnp.asarray([-2.0, -0.3, 0.0, 0.4, 2.0], dtype=jnp.float32)
    g = jax.grad(lambda v: ppa_act(tc, v).sum())(x)
    s = jax.nn.sigmoid(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(s * (1 - s)),
                               rtol=1e-5)


# ------------------------------------------------------------------ softmax
def test_ppa_softmax_close_to_exact(tab_exp2):
    tc = pack_table(tab_exp2)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(0, 4, size=(6, 333)), dtype=jnp.float32)
    y = np.asarray(ppa_softmax(tc, x))
    ref = np.asarray(jax.nn.softmax(x, axis=-1))
    assert np.abs(y - ref).max() < 5e-4
    np.testing.assert_allclose(y.sum(-1), 1.0, atol=1e-5)


def test_ppa_softmax_masking(tab_exp2):
    tc = pack_table(tab_exp2)
    x = jnp.zeros((2, 8), dtype=jnp.float32)
    mask = jnp.asarray([[1, 1, 1, 1, 0, 0, 0, 0],
                        [1, 0, 0, 0, 0, 0, 0, 0]], dtype=bool)
    y = np.asarray(ppa_softmax(tc, x, where=mask))
    np.testing.assert_allclose(y[0, :4], 0.25, atol=1e-4)
    np.testing.assert_allclose(y[0, 4:], 0.0)
    np.testing.assert_allclose(y[1, 0], 1.0, atol=1e-4)


def test_softmax_kernel_matches_wrapper(tab_exp2):
    tc = pack_table(tab_exp2)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(0, 3, size=(10, 200)), dtype=jnp.float32)
    y_k = np.asarray(softmax_ppa_2d(x, tc))
    y_w = np.asarray(ppa_softmax(tc, x))
    np.testing.assert_allclose(y_k, y_w, atol=1e-6)


def test_softmax_kernel_row_padding(tab_exp2):
    """Rows not divisible by block_m and cols not by 128."""
    tc = pack_table(tab_exp2)
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(0, 2, size=(5, 130)), dtype=jnp.float32)
    y = np.asarray(softmax_ppa_2d(x, tc))
    ref = np.asarray(ppa_softmax(tc, x))
    np.testing.assert_allclose(y, ref, atol=1e-6)


# ------------------------------------------------------------ segment select
#: segment counts around the bisection's 128-lane rows, the deployment
#: gate's 461 and the non-uniform silu@16 table's 516
SELECT_SEGMENTS = [1, 2, 127, 128, 129, 256, 461, 516]


@pytest.fixture(scope="module")
def tc_wide():
    return pack_table(get_table("sigmoid_wide", CFG16,
                                PPAScheme(order=2, quantizer="fqa")))


def _synthetic(tc, s: int):
    """``tc`` with ``s`` strictly increasing random starts in [lo, hi) and
    its coefficient rows repeated to ``s``."""
    rng = np.random.default_rng(s)
    starts = np.sort(rng.choice(np.arange(tc.lo, tc.hi), s, replace=False))
    coefs = np.resize(np.asarray(tc.coefs), (s, tc.coefs.shape[1]))
    return dataclasses.replace(tc, num_segments=s,
                               starts=jnp.asarray(starts, jnp.int32),
                               coefs=jnp.asarray(coefs, jnp.int32))


def _edge_ints(tc) -> np.ndarray:
    """Every start and the integer below it, the interval's ends and past
    them, with both signs."""
    st = np.asarray(tc.starts, np.int64)
    v = np.concatenate([st, st - 1, [tc.lo, tc.hi - 1, tc.hi, tc.hi + 37]])
    return np.concatenate([v, -v])


def _on(select: str, monkeypatch, fn):
    """``fn()`` with every kernel traced on the given segment select."""
    monkeypatch.setattr(body, "BISECT_MIN_SEGMENTS",
                        1 if select == "bisect" else 1 << 30)
    return np.asarray(fn())


@pytest.mark.parametrize("kernel", ["int", "fused", "fused_gated"])
@pytest.mark.parametrize("s", SELECT_SEGMENTS)
def test_select_paths_bit_exact(s, kernel, tc_wide, monkeypatch):
    """The bisection and the sweep select the same row as ``ref`` for every
    start, every start - 1, the interval's ends and beyond, both signs."""
    tc = _synthetic(tc_wide, s)
    ints = _edge_ints(tc)
    if kernel == "int":
        x = jnp.asarray(np.concatenate(
            [ints, [np.iinfo(np.int32).min, np.iinfo(np.int32).max]]),
            jnp.int32)
        run = lambda be: get_backend(be).eval_int(tc, x)
    else:
        op = ppa_gate if kernel == "fused_gated" else ppa_apply
        x = jnp.asarray(ints / float(1 << tc.w_in), jnp.float32)
        run = lambda be: op(tc, x, backend=be)
    be = "pallas" if kernel == "int" else "pallas_fused"
    ref = np.asarray(run("ref"))
    for select in ("bisect", "sweep"):
        np.testing.assert_array_equal(
            _on(select, monkeypatch, lambda: run(be)), ref,
            err_msg=f"{kernel} kernel on the {select}, S={s}")


def test_select_follows_segment_count(tab_exp2, tc_wide):
    """The select is chosen from the table's segment count alone: the
    14-segment exp2 table stays on the sweep, the 461-segment gate table
    bisects, and each kernel is named for the select it compiled with."""
    x = jnp.zeros((8, 128), jnp.float32)
    for tc, select in [(pack_table(tab_exp2), "sweep"), (tc_wide, "bisect")]:
        assert body.uses_bisection(tc.num_segments) == (select == "bisect")
        jaxpr = str(jax.make_jaxpr(lambda v: ppa_fused_apply(tc, v))(x))
        assert f"ppa_fused_{select}" in jaxpr, tc.num_segments
    assert (pack_table(tab_exp2).num_segments, tc_wide.num_segments) == \
        (14, 461)
