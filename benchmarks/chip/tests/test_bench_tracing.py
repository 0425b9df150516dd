"""The trace reduction, on a synthetic trace whose answer is worked by
hand, and on a small trace recorded on a TPU v5e
(five steps of two bf16 2048 x 2048 matmuls between the harness's host
spans)."""

import json
from pathlib import Path

import pytest

from fqabench import tracing

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000   # ns


def synthetic():
    """A 100 ms window: two device ops that overlap, a third later; host
    spans submit [0, 10), step [10, 60), poll [60, 100)."""
    ops = [("fusion.1", 12 * MS, 30 * MS),
           ("dot.2", 20 * MS, 40 * MS),
           ("fusion.1", 70 * MS, 80 * MS),
           ("outside", 150 * MS, 160 * MS)]
    host = [(tracing.WINDOW_SPAN, 0, 100 * MS),
            ("bench.submit", 0, 10 * MS),
            ("engine.step", 10 * MS, 60 * MS),
            ("bench.poll", 60 * MS, 100 * MS)]
    return tracing.Trace({"/device:TPU:0": ops}, host)


def test_reduce_synthetic():
    s = tracing.reduce_trace(synthetic())
    # busy = [12, 40) + [70, 80) = 38 ms of a 100 ms window
    assert s.window_s == pytest.approx(0.100)
    assert s.busy_s == pytest.approx(0.038)
    assert s.idle_share == pytest.approx(0.62)
    # op time inside the window, largest first; "outside" is out of it
    assert s.device_ops == [["fusion.1", pytest.approx(0.028)],
                            ["dot.2", pytest.approx(0.020)]]
    # idle: [0,12) mostly submit (10 of 12 ms), [40,70) mostly step
    # (20 of 30), [80,100) poll
    gaps = dict((k, v) for k, v in s.idle_gaps)
    assert gaps == {"bench.submit": pytest.approx(0.012),
                    "engine.step": pytest.approx(0.030),
                    "bench.poll": pytest.approx(0.020)}
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_reduce_averages_devices():
    t = synthetic()
    t.device_ops["/device:TPU:1"] = [("x", 0, 100 * MS)]
    s = tracing.reduce_trace(t)
    assert s.busy_s == pytest.approx((0.038 + 0.100) / 2)


def test_reduce_refuses_a_trace_without_the_window_or_the_device():
    t = synthetic()
    with pytest.raises(RuntimeError, match="TPU operations"):
        tracing.reduce_trace(tracing.Trace({}, t.host_spans))
    with pytest.raises(RuntimeError, match=tracing.WINDOW_SPAN):
        tracing.reduce_trace(tracing.Trace(t.device_ops, t.host_spans[1:]))


def test_merge():
    assert tracing.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_read_xplane_finds_host_spans(tmp_path):
    """On the CPU there is no device plane, but the harness's own spans
    must come back from a real profiler trace."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("engine.step"):
            f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    t = tracing.read_xplane(str(tmp_path))
    names = sorted(n for n, _, _ in t.host_spans)
    assert names == sorted([tracing.WINDOW_SPAN, "engine.step"])


def test_reduce_recorded_chip_trace():
    raw = json.loads((DATA / "trace_small.json").read_text())
    t = tracing.Trace({k: [tuple(e) for e in v]
                       for k, v in raw["device_ops"].items()},
                      [tuple(e) for e in raw["host_spans"]])
    s = tracing.reduce_trace(t)
    assert 0 < s.busy_s < s.window_s
    assert s.device_ops and s.idle_gaps
    # the host slept in bench.submit and bench.poll while the device idled
    assert {"bench.submit", "bench.poll"} <= {k for k, _ in s.idle_gaps}
    total_gap = sum(v for _, v in s.idle_gaps)
    assert total_gap == pytest.approx(s.window_s - s.busy_s, rel=1e-9)
