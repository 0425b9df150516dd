"""The program's own spans and scopes in a profiler trace.

``ServeEngine.step()`` writes host spans named ``serve.*`` with integer
arguments, and every activation of the model runs under a
``jax.named_scope("act.<name>")``, which the compiler keeps in each
device op's ``op_name``.  :func:`read_program` takes both out of the same
``.xplane.pb`` that :func:`tracing.read_xplane` reads, on the same clock;
:func:`reduce_program` turns them into the numbers of :data:`METRICS`.

Where the program writes no such span or scope (an older engine), the
readings that need them come out as None.

A TPU op event carries its scope in the ``tf_op`` stat of its event
metadata, keyed by the op's HLO text and its program's id; the "XLA
Modules" line gives each program execution as ``<name>(<program id>)``.
``ProfileData`` does not expose event metadata, so :func:`_op_scopes`
reads it from the protobuf's wire format (``tsl/profiler/protobuf/
xplane.proto``), which needs nothing beyond the standard library.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from . import tracing

SERVE_PREFIX = "serve."
#: a scope path component an activation writes
ACT_SCOPE = re.compile(r"(?:^|/)act\.[a-z_]+(?:[/:]|$)")
#: outermost ``serve.*`` span of each idle split
ADMIT_SPANS = ("serve.admit",)
DECODE_SPANS = ("serve.decode", "serve.sync", "serve.bookkeep")
DECODE_MODULE = "jit_serve_decode"
_MODULE = re.compile(r"^(.*)\((\d+)\)$")


@dataclasses.dataclass
class Span:
    name: str
    t0: int
    t1: int
    args: Dict[str, object]


@dataclasses.dataclass
class Op:
    t0: int
    t1: int
    module: str          # "" outside any traced program execution
    scope: str           # the op's op_name path; "" where unknown


@dataclasses.dataclass
class ProgramTrace:
    """What :func:`tracing.read_xplane` gives, unchanged, and beside it
    the program's spans, program executions and scoped device ops."""

    trace: tracing.Trace
    serve_spans: List[Span]
    modules: Dict[str, List[Tuple[str, int, int]]]     # dev -> executions
    ops: Dict[str, List[Op]]                           # dev -> ops


# ------------------------------------------------------------ wire format
def _varint(buf, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message in ``buf[i:end]``: an
    int for varints, a (start, end) pair for length-delimited fields."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            v, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf, se) -> str:
    return bytes(buf[se[0]:se[1]]).decode("utf-8", "replace")


def _op_scopes(data: bytes) -> Dict[Tuple[int, str], str]:
    """(program id, op event name) -> ``tf_op`` of every TPU plane's event
    metadata.  XSpace.planes = 1; XPlane.name = 2, event_metadata = 4,
    stat_metadata = 5 (map entries key = 1, value = 2); XEventMetadata.name
    = 2, stats = 5; XStat.metadata_id = 1, uint64 = 3, int64 = 4, str = 5,
    ref = 7; XStatMetadata.name = 2."""
    buf = memoryview(data)
    out: Dict[Tuple[int, str], str] = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, ev_meta, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g == 4:
                ev_meta.append(v)
            elif g == 5:
                key, val = 0, None
                for h, w in _fields(buf, *v):
                    if h == 1:
                        key = w
                    elif h == 2:
                        val = w
                if val is not None:
                    stat_names[key] = next(
                        (_text(buf, w) for h, w in _fields(buf, *val)
                         if h == 2), "")
        if not (name.startswith("/device:TPU:") and name[12:].isdigit()):
            continue
        for entry in ev_meta:
            meta = next((w for h, w in _fields(buf, *entry) if h == 2), None)
            if meta is None:
                continue
            op, pid, scope = "", None, None
            for h, w in _fields(buf, *meta):
                if h == 2:
                    op = _text(buf, w)
                elif h == 5:
                    stat, val = None, None
                    for k, x in _fields(buf, *w):
                        if k == 1:
                            stat = stat_names.get(x)
                        elif k in (3, 4, 5, 7):
                            val = (k, x)
                    if stat == "program_id" and val and val[0] in (3, 4):
                        pid = val[1]
                    elif stat == "tf_op" and val:
                        scope = (_text(buf, val[1]) if val[0] == 5 else
                                 stat_names.get(val[1], ""))
            if pid is not None and scope is not None:
                out[(pid, op)] = scope
    return out


# ----------------------------------------------------------------- reading
def _int_args(stats) -> Dict[str, object]:
    """A span's arguments: ints stay ints, a tuple of ints (written as
    its text) becomes a tuple."""
    out: Dict[str, object] = {}
    for k, v in stats:
        if isinstance(v, str) and v.startswith("("):
            out[k] = tuple(int(x) for x in re.findall(r"-?\d+", v))
        else:
            out[k] = v
    return out


def read_program(logdir: str) -> ProgramTrace:
    """Everything :func:`tracing.read_xplane` reads, and the ``serve.*``
    spans, the "XLA Modules" executions and each "XLA Ops" op's program
    and scope from the same file."""
    from jax.profiler import ProfileData

    base = tracing.read_xplane(logdir)
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    with open(path, "rb") as f:
        data = f.read()
    scopes = _op_scopes(data)
    by_name: Dict[str, set] = defaultdict(set)
    for (_, op), scope in scopes.items():
        by_name[op].add(scope)
    spans: List[Span] = []
    modules: Dict[str, List[Tuple[str, int, int]]] = {}
    ops: Dict[str, List[Op]] = {}
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            mods = modules.setdefault(plane.name, [])
            evs = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods.extend((ev.name, int(ev.start_ns),
                                 int(ev.start_ns) + int(ev.duration_ns))
                                for ev in line.events)
                elif line.name == "XLA Ops":
                    evs.extend((int(ev.start_ns),
                                int(ev.start_ns) + int(ev.duration_ns),
                                ev.name) for ev in line.events)
            mods.sort(key=lambda m: m[1])
            ops[plane.name] = _scoped_ops(sorted(evs), mods, scopes, by_name)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SERVE_PREFIX):
                        t0 = int(ev.start_ns)
                        spans.append(Span(ev.name, t0,
                                          t0 + int(ev.duration_ns),
                                          _int_args(ev.stats)))
    spans.sort(key=lambda s: (s.t0, -s.t1))
    return ProgramTrace(base, spans, modules, ops)


def _scoped_ops(evs, mods, scopes, by_name) -> List[Op]:
    """Each op event with the program execution it lies in and its scope,
    looked up by (program id, op) or, outside any execution, by the op
    alone where that is unambiguous."""
    out, j = [], 0
    for t0, t1, name in evs:
        while j < len(mods) and mods[j][2] <= t0:
            j += 1
        module, pid = "", None
        if j < len(mods) and mods[j][1] <= t0:
            m = _MODULE.match(mods[j][0])
            module = m.group(1) if m else mods[j][0]
            pid = int(m.group(2)) if m else None
        scope = scopes.get((pid, name))
        if scope is None:
            cands = by_name.get(name, ())
            scope = next(iter(cands)) if len(cands) == 1 else ""
        out.append(Op(t0, t1, module, scope))
    return out


# --------------------------------------------------------------- reducing
@dataclasses.dataclass
class ProgramSummary:
    window_s: float
    busy_s: float
    idle_by_span: Dict[str, float]     # innermost open span -> seconds
    idle_admit_s: Optional[float]      # None where no serve.* span is
    idle_decode_s: Optional[float]
    act_busy_s: Optional[float]        # None where no op carries a scope
    decode_device_ms: Optional[float]
    prefill_real_tokens: int
    prefill_padded_tokens: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def idle_gaps_program(self) -> List[list]:
        return [[k, v] for k, v in sorted(self.idle_by_span.items(),
                                          key=lambda kv: -kv[1])]


def _segments(spans: Sequence[Tuple[str, int, int]], lo: int, hi: int):
    """Pieces (a, b, innermost, outermost) that cover [lo, hi): the name of
    the innermost span open there and of the outermost ``serve.*`` one
    (None where none is).  Spans nest, as the context managers of one
    thread do."""
    out, stack, t = [], [], lo

    def emit(upto):
        nonlocal t
        upto = min(max(upto, lo), hi)
        if upto > t:
            outer = next((n for n, _ in stack
                          if n.startswith(SERVE_PREFIX)), None)
            out.append((t, upto, stack[-1][0] if stack else None, outer))
            t = upto

    def close():
        emit(stack[-1][1])
        stack.pop()

    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= a:
            close()
        emit(a)
        stack.append((name, b))
    while stack:
        close()
    emit(hi)
    return out


def _idle(busy: List[Tuple[int, int]], lo: int, hi: int):
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def _split(idle, segments) -> Dict[Tuple[str, Optional[str]], int]:
    """Nanoseconds of ``idle`` in each (innermost, outermost) label."""
    out: Dict[Tuple[str, Optional[str]], int] = defaultdict(int)
    j = 0
    for a, b in idle:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s0, s1, inner, outer = segments[k]
            ov = min(b, s1) - max(a, s0)
            if ov > 0:
                out[(inner or "other", outer)] += ov
            k += 1
    return out


def reduce_program(pt: ProgramTrace) -> ProgramSummary:
    """Idle time by the innermost span open at each moment (a ``serve.*``
    span, else the harness's own, else "other"), split by the outermost
    ``serve.*`` span into admission and decoding; the device time of ops
    under an ``act.*`` scope; the mean device time of one decode program;
    and the prompt tokens against the padded tokens prefilled."""
    wins = [(a, b) for n, a, b in pt.trace.host_spans
            if n == tracing.WINDOW_SPAN]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {tracing.WINDOW_SPAN} span, "
                           f"found {len(wins)}")
    lo, hi = wins[0]
    if not pt.ops:
        raise RuntimeError("the trace holds no TPU operations")
    harness = [(n, a, b) for n, a, b in pt.trace.host_spans
               if n in tracing.HOST_SPANS]
    serve = [(s.name, s.t0, s.t1) for s in pt.serve_spans]
    segs = _segments(harness + serve, lo, hi)
    n_dev = len(pt.ops)
    busy_ns = act_ns = 0
    idle_by: Dict[str, float] = defaultdict(float)
    admit = decode = 0
    any_scope = False
    dec_ms: List[float] = []
    for dev, ops in pt.ops.items():
        busy = tracing.merge(tracing._clip([(o.t0, o.t1) for o in ops],
                                           lo, hi))
        busy_ns += sum(b - a for a, b in busy)
        scoped = [(o.t0, o.t1) for o in ops if ACT_SCOPE.search(o.scope)]
        any_scope |= any(o.scope for o in ops)
        act_ns += sum(b - a for a, b in
                      tracing.merge(tracing._clip(scoped, lo, hi)))
        for (inner, outer), ns in _split(_idle(busy, lo, hi), segs).items():
            idle_by[inner] += ns
            admit += ns if outer in ADMIT_SPANS else 0
            decode += ns if outer in DECODE_SPANS else 0
        dec_ms.extend(_module_busy_ms(ops, pt.modules.get(dev, []),
                                      DECODE_MODULE, lo, hi))
    pre = [s for s in pt.serve_spans
           if s.name == "serve.prefill" and lo <= s.t0 < hi]
    return ProgramSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns / n_dev * 1e-9,
        idle_by_span={k: v / n_dev * 1e-9 for k, v in idle_by.items()},
        idle_admit_s=admit / n_dev * 1e-9 if serve else None,
        idle_decode_s=decode / n_dev * 1e-9 if serve else None,
        act_busy_s=act_ns / n_dev * 1e-9 if any_scope else None,
        decode_device_ms=sum(dec_ms) / len(dec_ms) if dec_ms else None,
        prefill_real_tokens=sum(int(s.args.get("real_tokens", 0))
                                for s in pre),
        prefill_padded_tokens=sum(int(s.args.get("padded_tokens", 0))
                                  for s in pre))


def _module_busy_ms(ops: List[Op], mods, module: str, lo: int,
                    hi: int) -> List[float]:
    """Device busy time (union of its op intervals), in ms, of each
    execution of ``module`` that lies wholly in [lo, hi)."""
    runs = [(a, b) for n, a, b in mods
            if n.split("(")[0] == module and lo <= a and b <= hi]
    mine = sorted((o.t0, o.t1) for o in ops if o.module == module)
    out, j = [], 0
    for a, b in runs:
        while j < len(mine) and mine[j][1] <= a:
            j += 1
        k, ivs = j, []
        while k < len(mine) and mine[k][0] < b:
            ivs.append(mine[k])
            k += 1
        out.append(sum(y - x for x, y in
                       tracing.merge(tracing._clip(ivs, a, b))) * 1e-6)
    return out


# ----------------------------------------------------------------- metrics
def act_device_share(s: ProgramSummary) -> Optional[float]:
    """Device time of ops under an ``act.*`` scope / device busy, %."""
    if s.act_busy_s is None or s.busy_s <= 0:
        return None
    return 100.0 * s.act_busy_s / s.busy_s


def idle_in_admit_share(s: ProgramSummary) -> Optional[float]:
    """Device idle while ``serve.admit`` is the outermost open ``serve.*``
    span / the traced window, %."""
    if s.idle_admit_s is None:
        return None
    return 100.0 * s.idle_admit_s / s.window_s


def idle_in_decode_share(s: ProgramSummary) -> Optional[float]:
    """As :func:`idle_in_admit_share`, for ``serve.decode``, the decode
    step's ``serve.sync`` and ``serve.bookkeep``."""
    if s.idle_decode_s is None:
        return None
    return 100.0 * s.idle_decode_s / s.window_s


def prefill_real_share(s: ProgramSummary) -> Optional[float]:
    """Prompt tokens / padded tokens over the window's prefills, %."""
    if not s.prefill_padded_tokens:
        return None
    return 100.0 * s.prefill_real_tokens / s.prefill_padded_tokens


def decode_device_ms(s: ProgramSummary) -> Optional[float]:
    """Mean device busy time of one ``jit_serve_decode`` execution, ms."""
    return s.decode_device_ms


#: the per-layer metrics these readings give, by name and cell suffix
METRICS = {
    "act.device_share": act_device_share,
    "device.idle_in_admit_share": idle_in_admit_share,
    "device.idle_in_decode_share": idle_in_decode_share,
    "model.decode_device_ms": decode_device_ms,
    "engine.prefill_real_share": prefill_real_share,
}
