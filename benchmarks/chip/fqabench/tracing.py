"""From a profiler trace to busy time, idle share and a breakdown.

:func:`read_xplane` takes the device operations and the harness's host
spans out of a ``jax.profiler`` trace; :func:`reduce_trace` turns them
into numbers.  The reduction is plain arithmetic on intervals, so the
tests check it on a synthetic trace.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

#: host spans the harness writes around its own calls
HOST_SPANS = ("bench.submit", "engine.step", "bench.poll")
#: the span that marks the traced window
WINDOW_SPAN = "bench.traced"

Interval = Tuple[int, int]          # [start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    """Device op events per device, and host spans by name."""

    device_ops: Dict[str, List[Tuple[str, int, int]]]   # dev -> (op, t0, t1)
    host_spans: List[Tuple[str, int, int]]              # (name, t0, t1)


@dataclasses.dataclass
class Summary:
    busy_s: float                   # mean over devices
    window_s: float
    device_ops: List[list]          # [[op name, seconds]], top 10
    idle_gaps: List[list]           # [[host span, seconds]], top 10

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def op_label(text: str) -> str:
    """A device op's event name is its whole HLO instruction; keep its
    name and the start of its type and operation, and a custom call's
    target (a Pallas kernel's ``tpu_custom_call``)."""
    name, _, rest = text.partition(" = ")
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    label = f"{name.lstrip('%')} = {rest[:90]}"
    return label + (f" [{target.group(1)}]" if target else "")


def read_xplane(logdir: str) -> Trace:
    """Parse the one ``*.xplane.pb`` the profiler wrote under ``logdir``:
    the "XLA Ops" line of each TPU plane, and the harness's spans from the
    host plane."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {logdir}, "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    ops: Dict[str, List[Tuple[str, int, int]]] = {}
    spans: List[Tuple[str, int, int]] = []
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            evs = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    t0 = int(ev.start_ns)
                    evs.append((op_label(ev.name), t0,
                                t0 + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        t0 = int(ev.start_ns)
                        spans.append((ev.name, t0, t0 + int(ev.duration_ns)))
    return Trace(ops, spans)


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def reduce_trace(trace: Trace, top: int = 10) -> Summary:
    """Busy seconds (union of op intervals, mean over devices), the traced
    window's length, the ops that took most device time, and the device's
    idle time inside the window by what the host was doing then."""
    wins = [(a, b) for n, a, b in trace.host_spans if n == WINDOW_SPAN]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(wins)}")
    lo, hi = wins[0]
    if not trace.device_ops:
        raise RuntimeError("the trace holds no TPU operations")
    busy_ns, per_op = [], defaultdict(int)
    gaps_by_span: Dict[str, int] = defaultdict(int)
    host = sorted((a, b, n) for n, a, b in trace.host_spans
                  if n in HOST_SPANS)
    for evs in trace.device_ops.values():
        for name, a, b in evs:
            per_op[name] += _overlap((a, b), (lo, hi))
        busy = merge(_clip([(a, b) for _, a, b in evs], lo, hi))
        busy_ns.append(sum(b - a for a, b in busy))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        j = 0                      # host spans are sorted and disjoint
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            while j < len(host) and host[j][1] <= a:
                j += 1
            best, who, k = 0, "other", j
            while k < len(host) and host[k][0] < b:
                ov = _overlap((a, b), host[k][:2])
                if ov > best:
                    best, who = ov, host[k][2]
                k += 1
            gaps_by_span[who] += b - a
    n_dev = len(trace.device_ops)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps_by_span.items(), key=lambda kv: -kv[1])[:top]
    return Summary(
        busy_s=sum(busy_ns) / n_dev * 1e-9,
        window_s=(hi - lo) * 1e-9,
        device_ops=[[k, v / n_dev * 1e-9] for k, v in ops if v > 0],
        idle_gaps=[[k, v / n_dev * 1e-9] for k, v in gaps])
