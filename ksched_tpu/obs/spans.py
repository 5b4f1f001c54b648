"""Hierarchical span tracing with Chrome/Perfetto trace-event export.

The flat `phases_ms` dict the round trace carried (runtime/trace.py)
could say *that* a round spent 7 ms in "solve" but not *where*: graph
export vs backend dispatch vs rung fallback vs decode. Spans make the
nesting first-class — round → schedule → {stats, graph_update, solve →
{graph_export, backend_solve → solver_rung…}, deltas, apply} — and the
whole tree exports as Chrome trace-event JSON that loads directly in
Perfetto / chrome://tracing.

Two-layer design, so instrumentation costs ~nothing when unused:

- `span(name, **args)` is a context manager that ALWAYS times (two
  `perf_counter` calls — exactly what the hand-rolled timing it
  replaces cost). `RoundTiming` in scheduler/flow_scheduler.py is
  populated from these spans' durations, which is what makes the round
  trace a *consumer* of the same measurements the live trace exports:
  the JSONL artifact and a captured Perfetto trace can never disagree.
- recording only happens while a `SpanTracer` is installed
  (`tracer.install()` / `with tracer:`); with none installed the span
  skips the contextvar parenting entirely.

Parenting is contextvar-based, so spans nest correctly across threads
and (if the host app uses them) asyncio tasks; each recorded event
carries its span id and parent span id in `args` in addition to the
time containment Perfetto uses for visual nesting. A span that exits
via an exception records `args.error` and still closes cleanly, so an
aborted round leaves a well-formed trace behind (the flight recorder
depends on that).
"""

from __future__ import annotations

import contextvars
import gc
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

_current: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "ksched_obs_span", default=None
)
_active: Optional["SpanTracer"] = None
_ids = itertools.count(1)


class Span:
    """One timed region. Use as a context manager, or manually via
    `start_span()` / `.finish()` when the region spans methods (the
    pipelined round's dispatch→finish gap)."""

    __slots__ = (
        "name", "args", "sid", "parent_sid", "parent_name",
        "t0_s", "t1_s", "dur_s", "_token", "_tracer",
    )

    def __init__(self, name: str, args: Optional[Dict] = None) -> None:
        self.name = name
        self.args = args
        self.sid = 0
        self.parent_sid = 0
        self.parent_name: Optional[str] = None
        self.t0_s = 0.0
        self.t1_s = 0.0
        self.dur_s = 0.0
        self._token = None
        self._tracer: Optional[SpanTracer] = None

    def set(self, key: str, value) -> None:
        """Attach an arg after entry (e.g. a superstep count only known
        once the solve returns)."""
        if self.args is None:
            self.args = {}
        self.args[key] = value

    def __enter__(self) -> "Span":
        tracer = _active
        self._tracer = tracer
        if tracer is not None:
            parent = _current.get()
            self.sid = next(_ids)
            if parent is not None:
                self.parent_sid = parent.sid
                self.parent_name = parent.name
            self._token = _current.set(self)
        self.t0_s = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.t1_s:
            return False  # already closed (error-path re-close is a no-op)
        t1 = time.perf_counter()
        self.t1_s = t1
        self.dur_s = t1 - self.t0_s
        tracer = self._tracer
        if tracer is not None:
            _current.reset(self._token)
            self._token = None
            if exc_type is not None:
                self.set("error", f"{exc_type.__name__}: {exc}")
            tracer._record(self)
        return False

    def finish(self) -> float:
        """Close a manually-started span; returns its duration."""
        self.__exit__(None, None, None)
        return self.dur_s


def span(name: str, **args) -> Span:
    """Open a (not-yet-entered) span; `with span("solve") as sp:`."""
    return Span(name, args or None)


def start_span(name: str, **args) -> Span:
    """Enter a span immediately (manual-finish form)."""
    return Span(name, args or None).__enter__()


def active_tracer() -> Optional["SpanTracer"]:
    return _active


def _chrome_event(name: str, t0_s: float, dur_s: float, args: Dict, pid: int) -> dict:
    """The one construction of a Chrome complete event, stamped with the
    calling thread: recorded spans, synthesized events and the
    collector's pauses must never fork the schema."""
    return {
        "ph": "X",
        "cat": "ksched",
        "name": name,
        "ts": t0_s * 1e6,  # perf_counter base: monotonic, shared in-process
        "dur": dur_s * 1e6,
        "pid": pid,
        "tid": threading.get_ident(),
        "args": args,
    }


# -- collector pauses --------------------------------------------------------
#
# A collection of the oldest generation stops every thread for as long
# as the heap is large (0.3-0.5 s over a 150,000-pod graph), and without
# a name it is charged to whichever span it fell into. While a tracer is
# installed a `gc.callbacks` hook times each collection; one that held
# the process for GC_PAUSE_FLOOR_S or longer becomes a `gc_pause` event
# (args `generation`, `collected`) of the tracer active at its end, on
# the thread that collected, parented to the span open there. Every
# pause, short or long, is summed (`gc_pause_total_s`): a young
# collection takes tens of microseconds and a busy round has hundreds,
# which is a number worth having and not an event each. The hook runs
# between two bytecodes of ANY code, the tracer's own locked sections
# included, so it takes no lock: it leaves the event on the tracer's
# `_gc_pending` (a list append) and that tracer's next record or read
# moves it into the ring.

GC_PAUSE_FLOOR_S = 1e-3
_gc_t0_s = 0.0
_gc_total_s = 0.0


def _gc_hook(phase: str, info: Dict) -> None:
    global _gc_t0_s, _gc_total_s
    if phase == "start":
        _gc_t0_s = time.perf_counter()
        return
    if not _gc_t0_s:
        return  # installed while a collection ran
    t0, _gc_t0_s = _gc_t0_s, 0.0
    dur = time.perf_counter() - t0
    _gc_total_s += dur
    tracer = _active
    if dur < GC_PAUSE_FLOOR_S or tracer is None:
        return
    args = {
        "generation": info.get("generation", -1),
        "collected": info.get("collected", 0),
        "sid": next(_ids),
    }
    parent = _current.get()
    if parent is not None:
        args["parent_sid"] = parent.sid
        args["parent"] = parent.name
    tracer._gc_pending.append(_chrome_event("gc_pause", t0, dur, args, tracer._pid))


def gc_pause_total_s() -> float:
    """Seconds the collector has held the process while a tracer was
    installed, all threads' collections together (each holds the GIL,
    so every thread waited): a round takes the difference between its
    start and its end (RoundRecord.gc_pause_ms)."""
    return _gc_total_s


def unwind(outer: Span, exc_type, exc, tb) -> None:
    """Error-path close for manual-span regions: close every open span
    from the current innermost up to and including `outer`, so the
    error is recorded on each and the contextvar parenting is restored
    for whatever runs next on this thread. A span entered with no
    tracer installed never touched the contextvar — then only `outer`
    itself needs closing (for its duration; nothing records)."""
    if outer._tracer is None or outer.t1_s:
        outer.__exit__(exc_type, exc, tb)
        return
    while True:
        cur = _current.get()
        if cur is None or cur.t1_s:
            # chain unexpectedly broken; still close outer
            outer.__exit__(exc_type, exc, tb)
            return
        done = cur is outer
        cur.__exit__(exc_type, exc, tb)
        if done:
            return


class SpanTracer:
    """Collects finished spans as Chrome trace events in a bounded ring.

    `mark()`/`events_since(mark)` slice out one round's spans for the
    flight recorder; `chrome_trace()`/`dump()` export the whole ring
    for Perfetto. Thread-safe: spans finish on whichever thread ran
    them (the service thread, watch threads, the watchdog timer)."""

    def __init__(self, capacity: int = 1 << 16) -> None:
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=capacity)
        self.capacity = capacity
        #: of the process that made the tracer, read once: a syscall an
        #: event is the larger part of a record under a sandboxed kernel
        self._pid = os.getpid()
        self.total = 0  # spans ever recorded (ring may have dropped some)
        self.dropped = 0
        #: collector pauses that ended while this tracer was the active
        #: one, until the next record or read takes them into the ring
        self._gc_pending: List[dict] = []
        self._prev: Optional[SpanTracer] = None

    # -- recording ---------------------------------------------------------

    def _append(self, name: str, t0_s: float, dur_s: float, args: Dict) -> None:
        """One Chrome-event construction + locked ring append for both
        recorded spans and synthesized events."""
        self._push(_chrome_event(name, t0_s, dur_s, args, self._pid))

    def _push(self, event: dict) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(event)
            self.total += 1

    def _take_gc_pauses(self) -> None:
        """Move the collector's pauses (_gc_hook) into the ring."""
        while self._gc_pending:
            try:
                event = self._gc_pending.pop(0)
            except IndexError:  # another thread took the last one
                return
            self._push(event)

    def _record(self, sp: Span) -> None:
        args = dict(sp.args) if sp.args else {}
        args["sid"] = sp.sid
        if sp.parent_sid:
            args["parent_sid"] = sp.parent_sid
            args["parent"] = sp.parent_name
        if self._gc_pending:
            self._take_gc_pauses()
        self._append(sp.name, sp.t0_s, sp.t1_s - sp.t0_s, args)

    def record_event(self, name: str, t0_s: float, dur_s: float, args: Optional[Dict] = None) -> None:
        """Record a SYNTHESIZED complete event (no live Span object):
        the solver-interior telemetry decode (obs/soltel.py) fabricates
        per-superstep child spans under a backend_solve span from
        device counters, apportioning the parent's wall time — the
        device cannot produce host timestamps itself. Events land in
        the same ring with the same schema as recorded spans."""
        self._append(name, t0_s, dur_s, dict(args) if args else {})

    # -- slicing (flight recorder) -----------------------------------------

    def mark(self) -> int:
        self._take_gc_pauses()
        with self._lock:
            return self.total

    def events_since(self, mark: int) -> List[dict]:
        """Events recorded after `mark` (oldest may be lost to the ring;
        what remains is returned). islice, not a full-ring copy: the
        flight recorder calls this every round to slice out the last
        ~dozen events of a ring that may hold 64k."""
        self._take_gc_pauses()
        with self._lock:
            want = self.total - mark
            skip = max(0, len(self._events) - want)
            return list(itertools.islice(self._events, skip, None))

    # -- export ------------------------------------------------------------

    def events(self) -> List[dict]:
        self._take_gc_pauses()
        with self._lock:
            return list(self._events)

    def chrome_trace(self) -> dict:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)

    # -- activation --------------------------------------------------------

    def install(self) -> "SpanTracer":
        """Make this the process-active tracer (stacking: uninstall
        restores the previous one). The first tracer installed hooks
        the collector (`gc_pause`); the last one uninstalled takes the
        hook away."""
        global _active
        self._prev = _active
        _active = self
        if _gc_hook not in gc.callbacks:
            gc.callbacks.append(_gc_hook)
        return self

    def uninstall(self) -> None:
        global _active
        if _active is self:
            _active = self._prev
        self._prev = None
        if _active is None and _gc_hook in gc.callbacks:
            gc.callbacks.remove(_gc_hook)

    def __enter__(self) -> "SpanTracer":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()
