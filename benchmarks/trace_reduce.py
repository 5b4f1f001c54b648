"""From a profiler trace and the program's spans to busy, idle and blame.

The reduction works on plain lists so that it can be checked on a small
synthetic trace (tests/benchmark): `load_xplane` is the only function
that touches `jax.profiler.ProfileData`.

Clocks. The device trace has its own clock; the program's spans and the
benchmark's stamps are `time.perf_counter()`. The benchmark writes two
`jax.profiler.TraceAnnotation("bench_anchor", t_ns=<perf_counter ns>)`
events, one right after the capture starts and one right before it
stops. Each anchor gives `trace_ns - perf_ns`; their mean is the offset,
their difference is the drift over the capture, and the traced window is
the time between them.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

ANCHOR = "bench_anchor"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
#: the line of a device plane that holds one event per executed HLO op
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]  # (start, end), any one unit
Op = Tuple[str, float, float]  # (name, start_ns, duration_ns)


@dataclass
class RawTrace:
    #: chip -> its op events, trace clock, ns
    device_ops: Dict[int, List[Op]] = field(default_factory=dict)
    #: (trace start_ns, perf_counter ns carried in the annotation)
    anchors: List[Tuple[float, float]] = field(default_factory=list)


def op_name(hlo: str) -> str:
    """`%fusion.4 = s32[131072]{0:T(1024)} fusion(...)` -> `fusion.4 s32[131072]`:
    the instruction's name and its result's shape, which is what stays
    the same from run to run."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    shape = re.match(r"\(?[a-z0-9]+\[[0-9,]*\]", rest)
    return (head.lstrip("%") + (" " + shape.group(0).lstrip("(") if shape else ""))[:80]


def load_xplane(path: str) -> RawTrace:
    """Device ops and anchors of one `.xplane.pb`. On an accelerator the
    ops are the `XLA Ops` line of each `/device:` plane; on the CPU
    (rehearsal only) they are the host events that carry an `hlo_op`."""
    from jax.profiler import ProfileData

    raw = RawTrace()
    data = ProfileData.from_file(path)
    host_ops: List[Op] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                if line.name == OPS_LINE:
                    raw.device_ops.setdefault(int(m.group(2)), []).extend(
                        (op_name(e.name), float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    )
                continue
            if not plane.name.startswith("/host:"):
                continue
            for e in line.events:
                if e.name == ANCHOR:
                    raw.anchors.append((float(e.start_ns), float(dict(e.stats)["t_ns"])))
                elif line.name.startswith("tf_XLA") and not e.name.startswith(
                    ("ThreadpoolListener", "end: ")
                ) and e.duration_ns > 0:
                    host_ops.append((e.name, float(e.start_ns), float(e.duration_ns)))
    if not raw.device_ops and host_ops:
        raw.device_ops[0] = host_ops
    raw.anchors.sort()
    return raw


# -- intervals ---------------------------------------------------------------


def merge_intervals(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


def busy_and_gaps(ops: Sequence[Op], t0: float, t1: float) -> Tuple[float, List[Interval]]:
    """Busy time (the union of the op intervals inside [t0, t1]) and the
    idle gaps between them, in the ops' unit."""
    merged = merge_intervals(clip(((s, s + d) for _n, s, d in ops), t0, t1))
    busy = sum(e - s for s, e in merged)
    gaps: List[Interval] = []
    at = t0
    for s, e in merged:
        if s > at:
            gaps.append((at, s))
        at = e
    if t1 > at:
        gaps.append((at, t1))
    return busy, gaps


def self_times(ops: Sequence[Op]) -> Dict[str, float]:
    """Time per op name with the time of the ops nested inside it taken
    out (a `while` spans its body's ops on the same line)."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, self]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, _end, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    close(float("inf"))
    return out


# -- what the host was doing ----------------------------------------------------


def flatten_spans(spans: Sequence[Tuple[str, float, float]]) -> List[Tuple[str, float, float]]:
    """Nested (name, start, end) spans of one thread, as disjoint
    segments each named after the deepest span that covers it."""
    segs: List[Tuple[str, float, float]] = []
    stack: List[Tuple[str, float]] = []  # (name, end)
    at = 0.0

    def emit(until: float) -> None:
        nonlocal at
        if stack and until > at:
            segs.append((stack[-1][0], at, until))
        at = max(at, until)

    for name, s, e in sorted(spans, key=lambda x: (x[1], -(x[2] - x[1]))):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        at = max(at, s)
        stack.append((name, e))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return segs


def attribute_gaps(gaps: Sequence[Interval],
                   segments: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle time by what the host was doing: each gap's time goes to the
    segments that overlap it, the rest to `unattributed`. Gaps and
    segments are on one clock."""
    out: Dict[str, float] = {}
    segs = sorted(segments, key=lambda x: x[1])
    j = 0
    for g0, g1 in sorted(gaps):
        covered = 0.0
        while j < len(segs) and segs[j][2] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][1] < g1:
            name, s, e = segs[k]
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
                covered += ov
            k += 1
        rest = (g1 - g0) - covered
        if rest > 0:
            out["unattributed"] = out.get("unattributed", 0.0) + rest
    return out


def clock_offset_ns(anchors: Sequence[Tuple[float, float]]) -> Tuple[float, float]:
    """(offset, drift) with offset = trace_ns - perf_ns, the mean over the
    anchors, and drift the difference between the last and the first."""
    if not anchors:
        raise ValueError("the trace holds no bench_anchor event")
    offs = [t - p for t, p in anchors]
    return sum(offs) / len(offs), offs[-1] - offs[0]


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce_trace(raw: RawTrace, spans: Sequence[Tuple[str, float, float]]) -> dict:
    """The traced window's numbers. `spans` are (name, start_s, end_s) on
    the perf_counter clock, nested, of the loop thread."""
    if len(raw.anchors) < 2:
        raise ValueError(f"want 2 bench_anchor events in the trace, found {len(raw.anchors)}")
    if not raw.device_ops:
        raise ValueError("the trace holds no device op")
    t0, t1 = raw.anchors[0][0], raw.anchors[-1][0]
    offset, drift = clock_offset_ns(raw.anchors)
    segments = [
        (n, s * 1e9 + offset, e * 1e9 + offset) for n, s, e in flatten_spans(spans)
    ]
    busy_ns: List[float] = []
    blame: Dict[str, float] = {}
    ops_self: Dict[str, float] = {}
    n_ops = 0
    for ops in raw.device_ops.values():
        inside = [(n, s, d) for n, s, d in ops if s + d > t0 and s < t1]
        n_ops += len(inside)
        busy, gaps = busy_and_gaps(inside, t0, t1)
        busy_ns.append(busy)
        for name, v in attribute_gaps(gaps, segments).items():
            blame[name] = blame.get(name, 0.0) + v
        for name, v in self_times(inside).items():
            ops_self[name] = ops_self.get(name, 0.0) + v
    chips = len(raw.device_ops)
    return {
        "window_s": (t1 - t0) / 1e9,
        "window_perf": ((t0 - offset) / 1e9, (t1 - offset) / 1e9),
        "busy_s": sum(busy_ns) / chips / 1e9,
        "chips": chips,
        "ops": n_ops,
        "clock_drift_us": drift / 1e3,
        "device_ops": top({k: v / chips / 1e9 for k, v in ops_self.items()}),
        "idle_gaps": top({k: v / chips / 1e9 for k, v in blame.items()}),
    }
