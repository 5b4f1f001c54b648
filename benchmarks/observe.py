"""What one run observed, in the shape the readers take it.

`rounds_from_spans` groups the program's spans by the `service_round`
that contains them; `Observation` is what every reader under readers/
gets: spans and round records of the window, the client's own series,
the reduced device trace (traced runs), counters and shapes.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from .stats import percentile


def reduce_values(values: Sequence[float], how: str) -> Optional[float]:
    """None when there is nothing to reduce: the metric is then left out."""
    if not len(values):
        return None
    if how.startswith("p") and how[1:].replace(".", "").isdigit():
        return percentile(values, float(how[1:]))
    if how == "mean":
        return float(sum(values)) / len(values)
    if how == "sum":
        return float(sum(values))
    if how == "max":
        return float(max(values))
    if how == "count":
        return float(len(values))
    raise ValueError(f"unknown reduction {how!r}")


@dataclass
class Round:
    t0: float  # perf_counter seconds
    t1: float
    pods: int
    solve: bool
    #: span name -> summed duration (ms) of the spans of that name inside
    spans_ms: Dict[str, float] = field(default_factory=dict)

    @property
    def solved(self) -> bool:
        """A round in which the scheduler ran (not an idle sweep, and not
        a sweep that found nothing runnable)."""
        return "round" in self.spans_ms


def rounds_from_spans(events: Sequence[dict]) -> List[Round]:
    """`events` are SpanTracer's Chrome events (ts and dur in us)."""
    svc = sorted(
        (e for e in events if e["name"] == "service_round"), key=lambda e: e["ts"]
    )
    rounds = [
        Round(
            t0=e["ts"] / 1e6, t1=(e["ts"] + e["dur"]) / 1e6,
            pods=int(e["args"].get("pods", 0)), solve=bool(e["args"].get("solve", True)),
        )
        for e in svc
    ]
    starts = [r.t0 for r in rounds]
    for e in events:
        if e["name"] == "service_round" or "sid" not in e["args"]:
            continue  # synthesized events (soltel's supersteps) carry no sid
        i = bisect.bisect_right(starts, e["ts"] / 1e6) - 1
        if i >= 0 and e["ts"] / 1e6 <= rounds[i].t1:
            r = rounds[i]
            r.spans_ms[e["name"]] = r.spans_ms.get(e["name"], 0.0) + e["dur"] / 1e3
    for r in rounds:
        r.spans_ms["service_round"] = (r.t1 - r.t0) * 1e3
    return rounds


@dataclass
class Observation:
    device_kind: str
    #: rounds of the window, from spans (traced runs; else empty)
    rounds: List[Round]
    #: RoundRecord dicts of the window
    records: List[dict]
    #: the client's own series: latency_ms, late_ms
    client: Dict[str, List[float]]
    #: counters the benchmark keeps: compiles_in_window, ...
    counters: Dict[str, float]
    #: nodes, arcs (padded, as solved), machines, task_classes, path
    shapes: Dict[str, object]
    #: trace_reduce.reduce_trace's result plus rounds and supersteps
    #: inside the traced window; None in an untraced run
    trace: Optional[dict] = None
    #: --rehearse-cpu: the host has no peaks on file, so no roofline
    rehearsal: bool = False
