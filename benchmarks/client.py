"""The benchmark's side of the control plane: the ClusterAPI the service
is built over, and the thread that plays the cluster's users.

`BenchClusterAPI` is `cluster.SyntheticClusterAPI` with three additions:
it stamps `time.perf_counter()` per pod in `assign_bindings`; it delivers
the due pod completions through `svc.complete_pod` from inside
`poll_pod_batch` (the loop thread, between rounds: the only point at which
tools/soak.py and chip_smoke.py call it); and it keeps an ordered log of
Bindings, completions and evictions for the replay in correct.py.

`TrafficDriver` submits what traffic.py planned: the class sweep, the
warm-up, the window, the drain and the closing round; then it closes the
API, which ends `SchedulerService.run`.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Set, Tuple

from ksched_tpu.cluster import SyntheticClusterAPI
from ksched_tpu.cluster.api import Binding, PodEvent

from .traffic import Plan, Pod

#: how long after the window unbound pods may still bind
DRAIN_S = 5.0
#: a phase outside the window that takes longer than this has hung
PHASE_TIMEOUT_S = 300.0
#: warm-up is extended, period by period, at most this many times
MAX_WARMUP_EXTENSIONS = 3


class DriverError(RuntimeError):
    """The traffic driver could not carry out its plan."""


class BenchClusterAPI(SyntheticClusterAPI):
    def __init__(self, pod_chan_size: int) -> None:
        super().__init__(pod_chan_size=pod_chan_size)
        self.svc = None  # set once the service is built
        self._due_completions: deque = deque()
        self._log_lock = threading.Lock()
        #: ("bind", pod, node, t), ("done", pod, "", t) and
        #: ("evict", pod, node, t) in the order the loop thread made them
        self.log: List[Tuple[str, str, str, float]] = []
        #: the pods whose newest entry in the log is an "evict": pending
        #: again, so their owner does not complete them
        self.evicted: Set[str] = set()
        #: pod -> stamps of every Binding posted for it
        self.bind_stamps: Dict[str, List[float]] = {}
        self._outstanding = 0
        self._all_bound = threading.Event()
        self._all_bound.set()
        #: (t0, t1, pods) of every poll_pod_batch: what the loop did
        #: between rounds, for the attribution of idle gaps
        self.polls: List[Tuple[float, float, int]] = []
        self.completions_refused = 0

    # -- the driver's side -------------------------------------------------

    def expect(self, n: int) -> None:
        with self._log_lock:
            self._outstanding += n
            self._all_bound.clear()

    def wait_all_bound(self, timeout_s: float) -> bool:
        return self._all_bound.wait(timeout_s)

    def forget_outstanding(self) -> None:
        with self._log_lock:
            self._outstanding = 0
            self._all_bound.set()

    def complete_later(self, pod_ids: List[str]) -> None:
        """One batch, delivered whole: the loop never sees half of a
        wave's completions and re-solves on them before its pods come."""
        self._due_completions.append(pod_ids)

    # -- the loop thread's side --------------------------------------------

    def _deliver_completions(self) -> None:
        due = self._due_completions
        while due:
            for pod_id in due.popleft():
                ok = self.svc.complete_pod(pod_id)
                with self._log_lock:
                    if ok:
                        self.log.append(("done", pod_id, "", time.perf_counter()))
                    else:
                        self.completions_refused += 1

    def poll_pod_batch(self, timeout_s: float) -> List[PodEvent]:
        t0 = time.perf_counter()
        self._deliver_completions()
        batch = super().poll_pod_batch(timeout_s)
        # completions queued before the pods of this batch were
        # submitted are delivered before the round that admits them
        self._deliver_completions()
        self.polls.append((t0, time.perf_counter(), len(batch)))
        return batch

    def assign_bindings(self, bindings: List[Binding]) -> None:
        t = time.perf_counter()
        super().assign_bindings(bindings)
        with self._log_lock:
            for b in bindings:
                self.log.append(("bind", b.pod_id, b.node_id, t))
                stamps = self.bind_stamps.setdefault(b.pod_id, [])
                if not stamps:
                    self._outstanding -= 1
                stamps.append(t)
            if self.evicted:
                self.evicted.difference_update(b.pod_id for b in bindings)
            if self._outstanding <= 0:
                self._all_bound.set()

    def evict_pods(self, evictions: List[Binding]) -> None:
        """The service took these pods off their nodes (preemption): each
        `Binding` names the pod and the node it leaves. One log entry for
        each, in the loop thread's order, then the parent's method where
        the program's ClusterAPI has one."""
        t = time.perf_counter()
        with self._log_lock:
            for e in evictions:
                self.log.append(("evict", e.pod_id, e.node_id, t))
                self.evicted.add(e.pod_id)
        parent = getattr(super(), "evict_pods", None)
        if parent is not None:
            parent(evictions)


class CompileWatch:
    """Counts JAX's backend-compile events (a program compiled, or loaded
    from the persistent cache: either way a shape the process had not
    run yet) with the time each ended."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.stamps: List[float] = []
        self.cache = {"requests": 0, "hits": 0, "misses": 0}
        names = {
            "/jax/compilation_cache/compile_requests_use_cache": "requests",
            "/jax/compilation_cache/cache_hits": "hits",
            "/jax/compilation_cache/cache_misses": "misses",
        }

        def on_duration(event, duration, **_kw):
            if event == self.EVENT:
                self.stamps.append(time.perf_counter())

        def on_event(event, **_kw):
            if event in names:
                self.cache[names[event]] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def count(self) -> int:
        return len(self.stamps)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.stamps if t0 <= t <= t1)


class TrafficDriver(threading.Thread):
    """Plays the plan against the API. One thread, no busy-waiting."""

    def __init__(self, api: BenchClusterAPI, plan: Plan, seconds: float,
                 compiles: CompileWatch, make_pod: Callable[[str, int], PodEvent]) -> None:
        super().__init__(name="bench-traffic", daemon=True)
        self.api = api
        self.plan = plan
        #: the cell's pods module over (config, seed): (pod id, class) -> PodEvent
        self.make_pod = make_pod
        self.seconds = seconds
        self.compiles = compiles
        self.victims: deque = deque(plan.victims)
        self.error: Optional[BaseException] = None
        self.window0 = 0.0
        self.window1 = 0.0
        self.window_ready = threading.Event()
        #: pod -> (due, submitted) for every pod due in the window
        self.due: Dict[str, Tuple[float, float]] = {}
        self.warmup_extensions = 0
        self.drain_s = 0.0
        #: when each phase of set-up ended (perf_counter)
        self.phase_ends: Dict[str, float] = {}

    # -- helpers -------------------------------------------------------------

    def _submit(self, pod: Pod) -> None:
        # built here, not ahead: the event stamps `received_s` as it is made
        self.api.submit_pod(self.make_pod(pod[0], pod[1]))
        self.victims.append(pod[0])

    def _complete_next(self, n: int) -> None:
        if n:
            self.api.complete_later([self._next_victim() for _ in range(n)])

    def _next_victim(self) -> str:
        """The head of `victims`; an evicted pod that is pending again goes
        to the tail instead (its owner would delete it, not complete it)."""
        for _ in range(len(self.victims)):
            pod = self.victims.popleft()
            if pod not in self.api.evicted:
                return pod
            self.victims.append(pod)
        raise DriverError("no pod is left to complete" + (
            ": every victim is evicted and pending" if self.victims else ""))

    def _wait_bound(self, what: str, timeout_s: float = PHASE_TIMEOUT_S,
                    must: bool = True) -> bool:
        deadline = time.perf_counter() + timeout_s
        while not self.api.wait_all_bound(0.25):
            if self.api.is_closed():
                raise DriverError(f"the API closed during {what}")
            if time.perf_counter() > deadline:
                if must:
                    raise DriverError(f"{what}: pods still unbound after {timeout_s:.0f} s")
                return False
        return True

    def _burst(self, pods: List[Pod], what: str) -> None:
        self.api.expect(len(pods))
        for pod in pods:
            self._submit(pod)
        self._wait_bound(what)

    # -- the plan ------------------------------------------------------------

    def run(self) -> None:
        try:
            self._wait_bound("the fill round")
            self.phase_ends["fill"] = time.perf_counter()
            for k, burst in enumerate(self.plan.class_sweep, 1):
                self._burst(burst, f"class sweep {k}")
            self.phase_ends["class_sweep"] = time.perf_counter()
            if self.plan.kind == "open_poisson":
                self._open_loop()
            else:
                self._closed_loop()
            # the drain: what is still unbound DRAIN_S after the window
            # has failed; the closing round waits for its own pods only
            self._wait_bound(
                "the drain", self.window1 + DRAIN_S - time.perf_counter(), must=False
            )
            self.drain_s = max(0.0, time.perf_counter() - self.window1)
            self.api.forget_outstanding()
            self._burst(self.plan.closing, "the closing round")
        except BaseException as e:  # noqa: BLE001 — handed to the main thread
            self.error = e
        finally:
            self.window_ready.set()
            self.api.close()

    def _open_loop(self) -> None:
        plan, api = self.plan, self.api
        start = time.perf_counter()
        period_end = start + plan.warmup_s
        mark = self.compiles.count()
        in_window = False
        i = 0
        while True:
            due = start + float(plan.arrival_offsets_s[i])
            if not in_window and due >= period_end:
                # warm-up ends with the first period in which nothing compiled
                if self.compiles.count() == mark or (
                    self.warmup_extensions >= MAX_WARMUP_EXTENSIONS
                ):
                    in_window = True
                    self.window0 = period_end
                    self.window1 = period_end + self.seconds
                    self.window_ready.set()
                else:
                    self.warmup_extensions += 1
                    mark = self.compiles.count()
                    period_end += plan.warmup_s
            if in_window and due >= self.window1:
                return
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            if api.is_closed():
                raise DriverError("the API closed during the open loop")
            pod = plan.arrival(i)
            self._complete_next(plan.completions_per_arrival)
            api.expect(1)
            self._submit(pod)
            if in_window:
                self.due[pod[0]] = (due, time.perf_counter())
            i += 1

    def _closed_loop(self) -> None:
        plan = self.plan
        k = 0
        while True:
            # warm-up: at least warmup_waves, ending on a wave in which
            # nothing compiled
            mark = self.compiles.count()
            self._one_wave(k, measured=False)
            k += 1
            clean = self.compiles.count() == mark
            if (k >= plan.warmup_waves and clean) or (
                k >= plan.warmup_waves * (1 + MAX_WARMUP_EXTENSIONS)
            ):
                break
        self.warmup_extensions = k - plan.warmup_waves
        self.window0 = time.perf_counter()
        self.window1 = self.window0 + self.seconds
        self.window_ready.set()
        while time.perf_counter() < self.window1:
            if not self._one_wave(k, measured=True):
                return  # a wave that never bound: its pods have failed
            k += 1

    def _one_wave(self, k: int, measured: bool) -> bool:
        pods = self.plan.wave(k)
        due = time.perf_counter()
        self._complete_next(len(pods))
        self.api.expect(len(pods))
        for pod in pods:
            self._submit(pod)
            if measured:
                self.due[pod[0]] = (due, time.perf_counter())
        if not measured:
            return self._wait_bound(f"wave {k}")
        return self._wait_bound(
            f"wave {k}", self.window1 + DRAIN_S - time.perf_counter(), must=False
        )
