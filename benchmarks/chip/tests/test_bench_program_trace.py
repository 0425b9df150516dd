"""The reading of the program's own spans and scopes from a trace: on a
synthetic trace whose answer is worked by hand, on a hand-encoded
protobuf, and on a small trace recorded on a TPU v5e (two layers of
internlm2-1.8b at published widths served through ``ServeEngine``: three
prefill groups, then decode steps), which also holds what an engine
without spans or scopes leaves: nothing to read, and no error."""

import gzip
import shutil
from pathlib import Path

import pytest

from fqabench import program_trace as P
from fqabench import tracing

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000   # ns


def synthetic():
    """A 100 ms window.  Host: submit [0, 10), step [10, 60), poll
    [60, 100); inside the step admit [10, 30) holding prefill [11, 20) and
    its sync [20, 25), then decode [30, 40), sync [40, 50), bookkeep
    [50, 55).  Device: a prefill program [19, 26) whose first op is the
    silu gate, a decode program [39, 48) whose first op is the softmax,
    and a decode program [95, 105) that outlasts the window."""
    def span(name, a, b, **args):
        return P.Span(name, a * MS, b * MS, args)

    ops = [P.Op(19 * MS, 24 * MS, "jit_serve_prefill",
                "jit(serve_prefill)/while/body/closed_call/act.silu/"
                "pallas_call:"),
           P.Op(24 * MS, 26 * MS, "jit_serve_prefill",
                "jit(serve_prefill)/dot_general:"),
           P.Op(39 * MS, 45 * MS, "jit_serve_decode",
                "jit(serve_decode)/act.softmax/exp:"),
           P.Op(45 * MS, 48 * MS, "jit_serve_decode",
                "jit(serve_decode)/dot_general:"),
           P.Op(95 * MS, 105 * MS, "jit_serve_decode", "")]
    host = [(tracing.WINDOW_SPAN, 0, 100 * MS),
            ("bench.submit", 0, 10 * MS),
            ("engine.step", 10 * MS, 60 * MS),
            ("bench.poll", 60 * MS, 100 * MS)]
    serve = [span("serve.admit", 10, 30, rows=2, groups=1),
             span("serve.prefill", 11, 20, bucket=4, rows=2, real_tokens=6,
                  padded_tokens=8, rids=(3, 4)),
             span("serve.sync", 20, 25, rows=2, phase=0),
             span("serve.decode", 30, 40, active=2),
             span("serve.sync", 40, 50, rows=2, phase=1),
             span("serve.bookkeep", 50, 55, finished=0)]
    mods = [("jit_serve_prefill(7)", 19 * MS, 26 * MS),
            ("jit_serve_decode(9)", 39 * MS, 48 * MS),
            ("jit_serve_decode(9)", 95 * MS, 105 * MS)]
    dev = "/device:TPU:0"
    trace = tracing.Trace({dev: [("op", o.t0, o.t1) for o in ops]}, host)
    return P.ProgramTrace(trace, serve, {dev: mods}, {dev: ops})


def test_reduce_synthetic():
    s = P.reduce_program(synthetic())
    # busy = [19, 26) + [39, 48) + [95, 100) = 21 ms
    assert s.window_s == pytest.approx(0.100)
    assert s.busy_s == pytest.approx(0.021)
    # idle [0, 19), [26, 39), [48, 95) by the innermost span at each moment
    assert s.idle_by_span == pytest.approx({
        "bench.submit": 0.010, "serve.admit": 0.001 + 0.004,
        "serve.prefill": 0.008, "serve.decode": 0.009, "serve.sync": 0.002,
        "serve.bookkeep": 0.005, "engine.step": 0.005, "bench.poll": 0.035})
    assert sum(v for _, v in s.idle_gaps_program()) == \
        pytest.approx(s.window_s - s.busy_s)
    assert s.idle_gaps_program()[0] == ["bench.poll", pytest.approx(0.035)]
    # admission: admit and the prefill inside it; decode: decode, its
    # sync and bookkeeping; the rest stays with the harness
    assert s.idle_admit_s == pytest.approx(0.013)
    assert s.idle_decode_s == pytest.approx(0.016)
    m = {k: f(s) for k, f in P.METRICS.items()}
    assert m["device.idle_in_admit_share"] == pytest.approx(13.0)
    assert m["device.idle_in_decode_share"] == pytest.approx(16.0)
    assert 100 * s.idle_share - m["device.idle_in_admit_share"] - \
        m["device.idle_in_decode_share"] == pytest.approx(50.0)
    # act.silu [19, 24) and act.softmax [39, 45): 11 of 21 busy ms
    assert m["act.device_share"] == pytest.approx(100 * 11 / 21)
    # the one decode program inside the window ran 9 ms on the device
    assert m["model.decode_device_ms"] == pytest.approx(9.0)
    assert m["engine.prefill_real_share"] == pytest.approx(75.0)


def test_segments_nest():
    segs = P._segments([("engine.step", 0, 10), ("serve.admit", 1, 6),
                        ("serve.prefill", 2, 4), ("serve.decode", 6, 9)],
                       0, 12)
    assert segs == [(0, 1, "engine.step", None),
                    (1, 2, "serve.admit", "serve.admit"),
                    (2, 4, "serve.prefill", "serve.admit"),
                    (4, 6, "serve.admit", "serve.admit"),
                    (6, 9, "serve.decode", "serve.decode"),
                    (9, 10, "engine.step", None),
                    (10, 12, None, None)]


def test_no_program_spans_or_scopes_reads_none():
    """An engine that writes no serve.* span and no act.* scope and whose
    programs are unnamed lambdas: the idle falls to the harness's spans,
    and every reading is None."""
    pt = synthetic()
    pt.serve_spans = []
    pt.ops = {d: [P.Op(o.t0, o.t1, "jit__lambda", "") for o in ops]
              for d, ops in pt.ops.items()}
    pt.modules = {d: [("jit__lambda(1)", a, b) for _, a, b in mods]
                  for d, mods in pt.modules.items()}
    s = P.reduce_program(pt)
    assert set(s.idle_by_span) == {"bench.submit", "engine.step",
                                   "bench.poll"}
    assert sum(s.idle_by_span.values()) == pytest.approx(0.079)
    assert {k: f(s) for k, f in P.METRICS.items()} == dict.fromkeys(
        P.METRICS)


def test_reduce_refuses_a_trace_without_the_window_or_the_device():
    pt = synthetic()
    pt.ops = {}
    with pytest.raises(RuntimeError, match="TPU operations"):
        P.reduce_program(pt)
    pt = synthetic()
    pt.trace.host_spans = pt.trace.host_spans[1:]
    with pytest.raises(RuntimeError, match=tracing.WINDOW_SPAN):
        P.reduce_program(pt)


def test_recorded_trace_small_json_reads_as_before():
    """PR 12's recorded chip trace, which holds no program span: the
    harness's own summary of it is what it was, and the program reading
    agrees on busy and window and finds nothing of its own to read."""
    import json
    raw = json.loads((DATA / "trace_small.json").read_text())
    t = tracing.Trace({k: [tuple(e) for e in v]
                       for k, v in raw["device_ops"].items()},
                      [tuple(e) for e in raw["host_spans"]])
    base = tracing.reduce_trace(t)
    assert base.busy_s == pytest.approx(0.001013423, rel=1e-9)
    assert base.window_s == pytest.approx(0.034385709, rel=1e-9)
    assert base.idle_gaps == [["bench.poll", pytest.approx(0.031092936)],
                              ["bench.submit", pytest.approx(0.00227935)]]
    assert base.device_ops[0][1] == pytest.approx(0.000909917)
    pt = P.ProgramTrace(t, [], {}, {d: [P.Op(a, b, "", "")
                                        for _, a, b in evs]
                                    for d, evs in t.device_ops.items()})
    s = P.reduce_program(pt)
    assert (s.busy_s, s.window_s) == (base.busy_s, base.window_s)
    assert sum(s.idle_by_span.values()) == pytest.approx(
        base.window_s - base.busy_s, rel=1e-9)
    assert all(f(s) is None for f in P.METRICS.values())


# ------------------------------------------------------------ wire format
def _v(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _len(num: int, payload: bytes) -> bytes:
    return _v(num << 3 | 2) + _v(len(payload)) + payload


def _int(num: int, n: int) -> bytes:
    return _v(num << 3) + _v(n)


def _plane(name, stat_names, events):
    """XPlane ``name`` with stat metadata {id: name} and event metadata
    [(id, name, [(stat id, field, value)])]."""
    body = _int(1, 1) + _len(2, name.encode())
    for sid, sname in stat_names.items():
        body += _len(5, _int(1, sid) + _len(2, _int(1, sid)
                                            + _len(2, sname.encode())))
    for eid, ename, stats in events:
        meta = _int(1, eid) + _len(2, ename.encode())
        for sid, field, val in stats:
            meta += _len(5, _int(1, sid) + (
                _len(field, val.encode()) if isinstance(val, str)
                else _int(field, val)))
        body += _len(4, _int(1, eid) + _len(2, meta))
    return _len(1, body)


def test_op_scopes_from_the_wire_format():
    names = {1: "program_id", 2: "tf_op", 3: "flops",
             4: "jit(f)/dot_general:"}
    tpu = _plane("/device:TPU:0", names, [
        (10, "%fusion.1 = f32[8]", [(1, 3, 7), (2, 5, "jit(f)/act.silu/x:"),
                                    (3, 4, 99)]),
        (11, "%dot.2 = f32[8]", [(1, 4, 9), (2, 7, 4)]),     # interned
        (12, "%copy-start.3 = f32[8]", [(1, 3, 7)])])        # no tf_op
    host = _plane("/host:CPU", names, [(10, "%fusion.1 = f32[8]",
                                        [(1, 3, 7), (2, 5, "host")])])
    other = _plane("/device:TPU:0 SparseCore", names, [
        (10, "%x = f32[8]", [(1, 3, 1), (2, 5, "sparse")])])
    space = tpu + host + other + _len(2, b"an error")
    assert P._op_scopes(space) == {
        (7, "%fusion.1 = f32[8]"): "jit(f)/act.silu/x:",
        (9, "%dot.2 = f32[8]"): "jit(f)/dot_general:"}


def test_span_arguments_read_as_ints():
    assert P._int_args([("rows", 3), ("rids", "(4, 5, -1)"),
                        ("one", "(7,)")]) == {"rows": 3, "rids": (4, 5, -1),
                                              "one": (7,)}


# ------------------------------------------------- the recorded chip trace
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("xplane") / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    with gzip.open(DATA / "trace_program_small.xplane.pb.gz", "rb") as f, \
            open(d / "t.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    return P.read_program(str(d.parents[2]))


def test_recorded_chip_trace_spans_and_scopes(recorded):
    pt = recorded
    names = [s.name for s in pt.serve_spans]
    assert names.count("serve.prefill") == 3
    assert {"serve.admit", "serve.insert_cache", "serve.decode",
            "serve.sync", "serve.bookkeep"} <= set(names)
    pre = [s.args for s in pt.serve_spans if s.name == "serve.prefill"]
    assert [(a["bucket"], a["rows"], a["rids"]) for a in pre] == [
        (512, 3, (100, 101, 102)), (256, 1, (103,)), (512, 2, (104, 105))]
    ops = pt.ops["/device:TPU:0"]
    assert {o.module for o in ops} >= {"jit_serve_prefill",
                                       "jit_serve_decode"}
    acts = {m.group(0).strip("/:") for o in ops
            for m in [P.ACT_SCOPE.search(o.scope)] if m}
    assert acts == {"act.silu", "act.softmax"}
    # each act op of a program lies in an execution of that program
    assert all(o.scope.startswith("jit(serve_")
               for o in ops if P.ACT_SCOPE.search(o.scope))


def test_recorded_chip_trace_reduces(recorded):
    pt = recorded
    base = tracing.reduce_trace(pt.trace)
    s = P.reduce_program(pt)
    assert (s.busy_s, s.window_s) == (base.busy_s, base.window_s)
    assert sum(v for _, v in s.idle_gaps_program()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    assert sum(v for _, v in base.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    assert {k for k, _ in s.idle_gaps_program()} <= {
        "serve.admit", "serve.prefill", "serve.sync", "serve.insert_cache",
        "serve.decode", "serve.bookkeep", "engine.step", "other"}
    m = {k: f(s) for k, f in P.METRICS.items()}
    assert 0 < m["act.device_share"] < 100
    rest = 100 * s.idle_share - m["device.idle_in_admit_share"] \
        - m["device.idle_in_decode_share"]
    assert 0 <= rest < 100 * s.idle_share
    assert m["engine.prefill_real_share"] == pytest.approx(
        100 * (1180 + 200 + 850) / (1536 + 256 + 1024))
    runs = [b - a for n, a, b in pt.modules["/device:TPU:0"]
            if n.startswith("jit_serve_decode(")]
    assert 0 < m["model.decode_device_ms"] <= max(runs) * 1e-6
