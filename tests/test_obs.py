"""Observability subsystem tests (ksched_tpu/obs).

Covers the exposition-correctness contract from the obs issue:
Prometheus text conformance (label escaping, `_bucket` monotonicity,
`_sum`/`_count` consistency with ingested samples), span nesting and
parenting under exceptions, flight-recorder dump triggers (deadline
miss, NOOP round, crash hook), zero-overhead no-op mode when obs is
disabled, the http_api stats-counter hammer, and live-endpoint
round-trips through a real socket.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from ksched_tpu.obs import (
    FlightRecorder,
    MetricsServer,
    Registry,
    SpanTracer,
    dump_registry,
    parse_prometheus,
    render_prometheus,
    scoped_registry,
    span,
    start_span,
)
from ksched_tpu.obs import metrics as obs_metrics
from ksched_tpu.obs.devprof import (
    ARC_RECORD_BYTES,
    DeviceProfiler,
    delta_nbytes,
    journal_nbytes,
)
from ksched_tpu.obs.metrics import NULL_REGISTRY, log_buckets
from ksched_tpu.runtime.trace import RoundRecord, RoundTracer


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = Registry()
    c = reg.counter("c_total", "a counter")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g", "a gauge")
    g.set(7)
    g.dec(3)
    assert g.value == 4
    h = reg.histogram("h_ms", "a histogram", buckets=(1, 2, 4))
    for v in (0.5, 1.5, 3, 100):
        h.observe(v)
    assert h.count == 4 and h.sum == 105.0


def test_labels_get_or_create_and_mismatch_errors():
    reg = Registry()
    fam = reg.counter("ev_total", "events", labelnames=("kind",))
    fam.labels(kind="a").inc()
    fam.labels("a").inc()  # positional form hits the same child
    assert reg.value("ev_total", kind="a") == 2
    assert reg.value("ev_total", kind="missing") == 0
    # same name again is get-or-create...
    assert reg.counter("ev_total", labelnames=("kind",)) is fam
    # ...but kind/label drift is a hard error
    with pytest.raises(ValueError):
        reg.gauge("ev_total")
    with pytest.raises(ValueError):
        reg.counter("ev_total", labelnames=("other",))
    with pytest.raises(ValueError):
        reg.counter("bad name!")
    # histogram bucket drift is a hard error too (silently landing
    # samples in bounds the caller did not ask for would skew every
    # percentile estimated from them)
    h = reg.histogram("lat2_ms", "latency", buckets=(1, 2, 4))
    assert reg.histogram("lat2_ms", buckets=(1, 2, 4)) is h
    assert reg.histogram("lat2_ms") is h  # unspecified accepts existing
    with pytest.raises(ValueError):
        reg.histogram("lat2_ms", buckets=(1, 2, 8))


def test_log_buckets_cover_range():
    b = log_buckets(1, 64, 2.0)
    assert b == (1, 2, 4, 8, 16, 32, 64)
    with pytest.raises(ValueError):
        log_buckets(0, 10)


# ---------------------------------------------------------------------------
# Prometheus text conformance
# ---------------------------------------------------------------------------


def test_exposition_label_escaping_round_trips():
    reg = Registry()
    fam = reg.counter("esc_total", 'help with \\ and\nnewline', labelnames=("k",))
    nasty = 'a"b\\c\nd'
    fam.labels(k=nasty).inc(3)
    text = render_prometheus(reg)
    assert '\\"' in text and "\\n" in text and "\\\\" in text
    parsed = parse_prometheus(text)
    assert parsed[("esc_total", (("k", nasty),))] == 3


def test_exposition_bucket_monotonicity_and_sum_count():
    reg = Registry()
    h = reg.histogram("lat_ms", "latency", buckets=(1, 10, 100))
    samples = [0.5, 0.5, 5, 50, 500, 7, 1]  # incl. exact bound (le semantics)
    for v in samples:
        h.observe(v)
    parsed = parse_prometheus(render_prometheus(reg))
    buckets = sorted(
        (float("inf") if dict(k[1])["le"] == "+Inf" else float(dict(k[1])["le"]), v)
        for k, v in parsed.items()
        if k[0] == "lat_ms_bucket"
    )
    # cumulative and non-decreasing, +Inf equals _count
    counts = [v for _, v in buckets]
    assert counts == sorted(counts)
    assert counts[-1] == parsed[("lat_ms_count", ())] == len(samples)
    assert parsed[("lat_ms_sum", ())] == pytest.approx(sum(samples))
    # le="1" holds the two 0.5s and the exact 1 (le is inclusive)
    assert buckets[0] == (1.0, 3)


def test_exposition_served_over_http():
    reg = Registry()
    reg.counter("served_total", "x").inc(5)
    srv = MetricsServer(port=0, registry=reg)
    try:
        with urllib.request.urlopen(srv.url + "/metricsz", timeout=5) as r:
            assert "version=0.0.4" in r.headers["Content-Type"]
            text = r.read().decode()
        assert parse_prometheus(text)[("served_total", ())] == 5
        with urllib.request.urlopen(srv.url + "/healthz", timeout=5) as r:
            assert json.loads(r.read())["status"] == "ok"
        with urllib.request.urlopen(srv.url + "/varz", timeout=5) as r:
            assert json.loads(r.read())["served_total"]["samples"][0]["value"] == 5
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(srv.url + "/nope", timeout=5)
        assert exc.value.code == 404
    finally:
        srv.stop()


def test_registry_snapshot_dump(tmp_path):
    reg = Registry()
    reg.histogram("h_ms", "h", buckets=(1, 2)).observe(1.5)
    path = tmp_path / "snap.json"
    dump_registry(reg, str(path))
    doc = json.loads(path.read_text())
    sample = doc["metrics"]["h_ms"]["samples"][0]
    assert sample["count"] == 1 and sample["buckets"][-1][0] == "+Inf"


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_nesting_and_parenting():
    tracer = SpanTracer()
    with tracer:
        with span("outer") as outer:
            with span("inner", k=1):
                pass
            with span("inner2"):
                pass
    events = {e["name"]: e for e in tracer.events()}
    assert events["inner"]["args"]["parent"] == "outer"
    assert events["inner2"]["args"]["parent_sid"] == outer.sid
    assert "parent" not in events["outer"]["args"]
    # time containment (what Perfetto uses for visual nesting)
    assert events["outer"]["ts"] <= events["inner"]["ts"]
    assert (
        events["inner"]["ts"] + events["inner"]["dur"]
        <= events["outer"]["ts"] + events["outer"]["dur"] + 1e-6
    )


def test_span_exception_records_error_and_restores_parent():
    tracer = SpanTracer()
    with tracer:
        with span("root"):
            with pytest.raises(RuntimeError):
                with span("fails"):
                    raise RuntimeError("boom")
            with span("after"):
                pass
    events = {e["name"]: e for e in tracer.events()}
    assert "RuntimeError: boom" in events["fails"]["args"]["error"]
    # the failed span unwound cleanly: the next span parents to root
    assert events["after"]["args"]["parent"] == "root"


def test_unwind_closes_open_manual_spans():
    # the manual-span error path (bulk.py _round_layered): an exception
    # with stats/decode spans still open must close the whole chain so
    # later spans are not mis-parented under a dead span
    import sys

    from ksched_tpu.obs.spans import unwind

    tracer = SpanTracer()
    with tracer:
        outer = start_span("round")
        start_span("decode")  # left open, as a mid-body exception would
        try:
            raise RuntimeError("boom")
        except RuntimeError:
            unwind(outer, *sys.exc_info())
        with span("next_round"):
            pass
    events = {e["name"]: e for e in tracer.events()}
    assert "RuntimeError: boom" in events["decode"]["args"]["error"]
    assert "RuntimeError: boom" in events["round"]["args"]["error"]
    assert "parent" not in events["next_round"]["args"]  # top-level again
    # without a tracer, unwind still closes the outer span for timing
    sp = start_span("untraced_round")
    unwind(sp, None, None, None)
    assert sp.dur_s > 0


def test_span_not_recorded_without_tracer():
    tracer = SpanTracer()
    with span("untraced"):
        pass
    assert tracer.events() == []
    sp = start_span("also_untraced")
    assert sp.finish() >= 0.0  # still times


def test_span_double_close_is_noop():
    tracer = SpanTracer()
    with tracer:
        sp = start_span("once")
        sp.finish()
        d = sp.dur_s
        sp.finish()
        assert sp.dur_s == d
    assert len(tracer.events()) == 1


def test_tracer_ring_and_slicing():
    tracer = SpanTracer(capacity=4)
    with tracer:
        for i in range(3):
            with span(f"s{i}"):
                pass
        mark = tracer.mark()
        for i in range(3):
            with span(f"t{i}"):
                pass
    assert tracer.total == 6 and tracer.dropped == 2
    since = [e["name"] for e in tracer.events_since(mark)]
    assert since == ["t0", "t1", "t2"]
    doc = tracer.chrome_trace()
    assert len(doc["traceEvents"]) == 4  # ring capacity


def test_tracer_install_stacks():
    a, b = SpanTracer(), SpanTracer()
    a.install()
    b.install()
    with span("inner_only"):
        pass
    b.uninstall()
    with span("outer_only"):
        pass
    a.uninstall()
    assert [e["name"] for e in b.events()] == ["inner_only"]
    assert [e["name"] for e in a.events()] == ["outer_only"]


# ---------------------------------------------------------------------------
# no-op mode
# ---------------------------------------------------------------------------


def test_disabled_obs_is_inert():
    assert obs_metrics.enabled()
    obs_metrics.set_enabled(False)
    try:
        reg = obs_metrics.get_registry()
        assert reg is NULL_REGISTRY
        c = reg.counter("anything_total", "x", labelnames=("k",))
        c.labels(k="a").inc(100)
        c.inc()
        c.observe(5)
        assert c.value == 0 and reg.collect() == [] and reg.snapshot() == {}
        assert render_prometheus(reg) == ""
    finally:
        obs_metrics.set_enabled(True)


def test_scoped_registry_swaps_and_restores():
    outer = obs_metrics.get_registry()
    with scoped_registry() as reg:
        assert obs_metrics.get_registry() is reg
        reg.counter("scoped_total", "x").inc()
    assert obs_metrics.get_registry() is outer
    assert reg.value("scoped_total") == 1


def test_scoped_registry_nests():
    with scoped_registry() as outer:
        outer.counter("outer_total", "x").inc()
        with scoped_registry() as inner:
            assert obs_metrics.get_registry() is inner
            inner.counter("inner_total", "x").inc()
        assert obs_metrics.get_registry() is outer
    assert outer.value("outer_total") == 1 and outer.value("inner_total") == 0


def test_scoped_registry_is_thread_confined():
    """The multi-tenant safety property: concurrent scopes in different
    threads must not clobber each other (the old process-global swap
    did), and a scope never leaks into an unscoped thread."""
    base = obs_metrics.get_registry()
    errors = []
    barrier = threading.Barrier(4)

    def run(i):
        try:
            with scoped_registry() as reg:
                barrier.wait(timeout=5)  # every thread inside a scope at once
                assert obs_metrics.get_registry() is reg
                reg.counter("private_total", "x").inc(i + 1)
                barrier.wait(timeout=5)
                assert obs_metrics.get_registry() is reg
                assert reg.value("private_total") == i + 1  # no cross-talk
            assert obs_metrics.get_registry() is base
        except Exception as e:  # noqa: BLE001 — surfaced via the errors list
            errors.append((i, e))
            try:
                barrier.abort()
            except Exception:  # noqa: BLE001
                pass

    threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not errors, errors
    assert obs_metrics.get_registry() is base


def test_scoped_registry_out_of_order_exit_is_an_error():
    a = scoped_registry()
    b = scoped_registry()
    a.__enter__()
    b.__enter__()
    with pytest.raises(RuntimeError):
        a.__exit__(None, None, None)
    b.__exit__(None, None, None)
    a.__exit__(None, None, None)


def test_registry_scoped_label_views():
    """Registry.scoped(tenant=...) views share ONE parent family with
    the scope label prepended; per-view samples never alias."""
    reg = Registry()
    a = reg.scoped(tenant="a")
    b = reg.scoped(tenant="b")
    a.counter("ksched_rt_total", "x").inc(2)
    b.counter("ksched_rt_total", "x").inc(5)
    assert reg.value("ksched_rt_total", tenant="a") == 2
    assert reg.value("ksched_rt_total", tenant="b") == 5
    assert a.value("ksched_rt_total") == 2
    # labelled families compose: scope labels come first
    fam = a.counter("ksched_rt_kinds_total", "x", labelnames=("kind",))
    fam.labels(kind="noop").inc()
    assert reg.value("ksched_rt_kinds_total", tenant="a", kind="noop") == 1
    assert reg.value("ksched_rt_kinds_total", tenant="b", kind="noop") == 0
    # histograms keep their buckets through the view
    h = b.histogram("ksched_rt_ms", "x", buckets=(1, 2, 4))
    h.observe(3)
    assert b.value("ksched_rt_ms") == 1
    # the text exposition carries the tenant label
    text = render_prometheus(reg)
    assert 'ksched_rt_total{tenant="a"} 2' in text
    # nested scoping accumulates labels
    ab = a.scoped(shard="0")
    ab.counter("ksched_rt_nested_total", "x").inc()
    assert reg.value("ksched_rt_nested_total", tenant="a", shard="0") == 1


def test_registry_scoped_label_collision_is_an_error():
    reg = Registry()
    view = reg.scoped(tenant="a")
    with pytest.raises(ValueError):
        view.counter("ksched_collide_total", "x", labelnames=("tenant",))
    # and a scope-labelled name cannot silently alias an unscoped one
    reg.counter("ksched_plain_total", "x")
    with pytest.raises(ValueError):
        view.counter("ksched_plain_total", "x")


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def _rec(i, **kw):
    rec = RoundRecord(round_index=i, wall_time=0.0, phases_ms={"total": 1.0})
    for k, v in kw.items():
        setattr(rec, k, v)
    return rec


def test_flight_dumps_on_deadline_miss_and_noop(tmp_path):
    reg = Registry()
    fl = FlightRecorder(capacity=4, dump_dir=str(tmp_path), registry=reg,
                        min_rounds_between_dumps=3)
    for i in range(3):
        assert fl.note_round(_rec(i)) is None
    path = fl.note_round(_rec(3, deadline_miss=True), span_events=[{"ph": "X", "name": "round"}])
    assert path is not None
    doc = json.loads(open(path).read())
    assert doc["reason"] == "deadline_miss"
    assert len(doc["rounds"]) == 4  # ring capacity
    assert doc["rounds"][-1]["record"]["deadline_miss"] is True
    assert doc["traceEvents"] == [{"ph": "X", "name": "round"}]
    # a NOOP round is a different trigger kind: dumps immediately
    assert fl.note_round(_rec(4, noop_round=True)) is not None
    # rate limit: another miss right away is suppressed...
    assert fl.note_round(_rec(5, deadline_miss=True)) is None
    # ...but fires again once the window passes
    assert fl.note_round(_rec(6, deadline_miss=True)) is not None
    assert reg.value("ksched_flight_dumps_total", reason="deadline_miss") == 2
    assert reg.value("ksched_flight_dumps_total", reason="noop_round") == 1


def test_degrading_solver_rung_gauge_starts_at_minus_one():
    # before the first solve lands, ksched_solver_rung must read -1
    # ("none yet"), not 0 (the top production rung)
    from ksched_tpu.runtime.degrade import DegradingSolver

    with scoped_registry() as reg:
        DegradingSolver([("only", object())])
        assert reg.value("ksched_solver_rung") == -1


def test_flight_dump_creates_missing_dir(tmp_path):
    # --flight-dir on a fresh checkout: the dir does not exist yet, and
    # a failed dump must not kill the service loop it post-mortems
    fl = FlightRecorder(capacity=2, dump_dir=str(tmp_path / "flight"),
                        registry=Registry(), min_rounds_between_dumps=1)
    path = fl.note_round(_rec(0, deadline_miss=True))
    assert path is not None and json.loads(open(path).read())["reason"] == "deadline_miss"


def test_flight_scope_discriminates_same_round_dumps(tmp_path):
    """REGRESSION (multi-tenant satellite): auto-dump filenames were
    round-keyed only, so two tenants dumping in the same round
    clobbered each other. Scoped recorders must write distinct files,
    and even an unscoped name collision falls back to a suffix instead
    of overwriting."""
    reg = Registry()
    a = FlightRecorder(capacity=2, dump_dir=str(tmp_path), registry=reg,
                       min_rounds_between_dumps=1, scope="tenant_a")
    b = FlightRecorder(capacity=2, dump_dir=str(tmp_path), registry=reg,
                       min_rounds_between_dumps=1, scope="tenant_b")
    pa = a.note_round(_rec(0, noop_round=True))
    pb = b.note_round(_rec(0, noop_round=True))
    assert pa != pb and pa is not None and pb is not None
    assert "tenant_a" in pa and "tenant_b" in pb
    assert json.loads(open(pa).read())["scope"] == "tenant_a"
    # unscoped recorders at the same round index no longer clobber
    u1 = FlightRecorder(capacity=2, dump_dir=str(tmp_path), registry=reg,
                        min_rounds_between_dumps=1)
    u2 = FlightRecorder(capacity=2, dump_dir=str(tmp_path), registry=reg,
                        min_rounds_between_dumps=1)
    p1 = u1.note_round(_rec(0, noop_round=True))
    p2 = u2.note_round(_rec(1, noop_round=True))
    assert p1 != p2
    assert json.loads(open(p1).read())["rounds"][0]["record"]["round_index"] == 0
    assert json.loads(open(p2).read())["rounds"][0]["record"]["round_index"] == 1


def test_flight_scope_filters_stall_attribution(tmp_path):
    """Tenant-scoped dumps carry only their own (or untagged) soltel
    stall events; stall_scope tags events with the ambient tenant."""
    from ksched_tpu.obs import soltel

    soltel.reset_stalls()
    with scoped_registry():
        with soltel.stall_scope("tenant_a"):
            soltel.note_stall({"kind": "excess_plateau"})
        with soltel.stall_scope("tenant_b"):
            soltel.note_stall({"kind": "eps_plateau"})
        soltel.note_stall({"kind": "backend_error"})  # untagged
        fl = FlightRecorder(capacity=2, dump_dir=str(tmp_path),
                            registry=Registry(), scope="tenant_a",
                            min_rounds_between_dumps=1)
        path = fl.note_round(_rec(0, noop_round=True))
    stalls = json.loads(open(path).read())["solver_stalls"]
    kinds = {s["kind"] for s in stalls}
    assert kinds == {"excess_plateau", "backend_error"}
    assert {s.get("tenant") for s in stalls} == {"tenant_a", None}
    soltel.reset_stalls()


def test_flight_crash_hook_chains(tmp_path):
    import sys

    reg = Registry()
    fl = FlightRecorder(capacity=2, dump_dir=str(tmp_path), registry=reg)
    fl.note_round(_rec(0))
    seen = []
    prev_hook = sys.excepthook
    sys.excepthook = lambda *a: seen.append(a)
    try:
        fl.install_crash_hook()
        fl.install_crash_hook()  # idempotent
        try:
            raise ValueError("simulated crash")
        except ValueError:
            sys.excepthook(*sys.exc_info())
        assert len(seen) == 1  # previous hook still ran
        assert len(fl.dumps) == 1 and "crash" in fl.dumps[0]
    finally:
        fl.uninstall_crash_hook()
        sys.excepthook = prev_hook


# ---------------------------------------------------------------------------
# devprof
# ---------------------------------------------------------------------------


def test_devprof_accounting():
    class P:
        num_arcs = 7
        num_nodes = 5
        cost = np.zeros(7, np.int32)
        cap = np.zeros(7, np.int32)

    class Stats:
        nodes_added = 2
        nodes_removed = 1
        arcs_added = 3
        arcs_changed = 4
        arcs_removed = 0

    reg = Registry()
    prof = DeviceProfiler(registry=reg)
    prof.note_export(P(), full=True)
    assert reg.value("ksched_h2d_bytes_total", kind="full_build") == 7 * 4 * 2
    prof.note_export(P(), full=False, stats=Stats())
    assert reg.value("ksched_h2d_bytes_total", kind="delta") == delta_nbytes(Stats())
    assert delta_nbytes(Stats()) == 7 * ARC_RECORD_BYTES + 3 * 9

    # journal form: counted from the applied changes themselves (arc
    # records carry src/dst), the exact scatter the round shipped
    class ArcChange:
        src, dst = 1, 2

    class NodeChange:
        pass

    before = reg.value("ksched_h2d_bytes_total", kind="delta")
    prof.note_export(P(), full=False, changes=[ArcChange(), ArcChange(), NodeChange()])
    assert journal_nbytes([ArcChange(), ArcChange(), NodeChange()]) == (
        2 * ARC_RECORD_BYTES + 9
    )
    assert (
        reg.value("ksched_h2d_bytes_total", kind="delta") - before
        == 2 * ARC_RECORD_BYTES + 9
    )

    class Backend:
        last_rung_name = "jax"
        last_iterations = 12

    class Result:
        iterations = 0

    prof.solve_starting()
    prof.note_solve(Backend(), P(), Result())
    assert reg.value("ksched_solves_total", backend="jax") == 1
    assert reg.value("ksched_solver_work", backend="jax") == 1  # one observation


# ---------------------------------------------------------------------------
# http_api stats hammer (the counters race the watch threads fixed)
# ---------------------------------------------------------------------------


def test_http_api_stats_hammer():
    from ksched_tpu.cluster.http_api import HTTPClusterAPI

    # poll_interval huge: the watch threads sleep on the stop event and
    # never touch the network, leaving the counters to the hammer
    api = HTTPClusterAPI("http://127.0.0.1:1", poll_interval_s=3600.0)
    try:
        keys = ("binding_retries", "binding_drops", "watch_retries")
        n_threads, n_inc = 8, 500
        start = threading.Barrier(n_threads)

        def hammer(k):
            start.wait()
            for _ in range(n_inc):
                api._count(k)

        threads = [
            threading.Thread(target=hammer, args=(keys[i % len(keys)],))
            for i in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        got = api.stats()
        per_key = {k: n_inc * sum(1 for i in range(n_threads) if keys[i % 3] == k)
                   for k in keys}
        assert got == per_key, f"lost updates: {got} != {per_key}"
    finally:
        api.close()


def test_http_api_private_registries_do_not_alias():
    from ksched_tpu.cluster.http_api import HTTPClusterAPI

    a = HTTPClusterAPI("http://127.0.0.1:1", poll_interval_s=3600.0)
    b = HTTPClusterAPI("http://127.0.0.1:1", poll_interval_s=3600.0)
    try:
        a._count("binding_retries", 3)
        assert a.stats() == {"binding_retries": 3}
        assert b.stats() == {}
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# RoundTracer <-> registry reconciliation + idle-sweep summary
# ---------------------------------------------------------------------------


def test_tracer_publishes_records_to_registry():
    reg = Registry()
    tracer = RoundTracer(registry=reg)
    for i in range(3):
        tracer._append(_rec(i, num_scheduled=2, faults_injected={"binding_drop": 1},
                            retries=2, degradations=1))
    tracer._append(_rec(3, solver_rung=-1))  # idle sweep
    tracer._append(_rec(4, solver_rung=-1, noop_round=True, deadline_miss=True))
    assert reg.value("ksched_rounds_total", kind="sched") == 3
    assert reg.value("ksched_rounds_total", kind="idle") == 1
    assert reg.value("ksched_rounds_total", kind="noop") == 1
    assert reg.value("ksched_scheduled_tasks_total") == 6
    assert reg.value("ksched_faults_attributed_total", kind="binding_drop") == 3
    assert reg.value("ksched_retries_total") == 6
    assert reg.value("ksched_round_degradations_total") == 3
    assert reg.value("ksched_deadline_misses_total") == 1
    # phase histogram only sees the 3 solved rounds
    assert reg.value("ksched_round_phase_ms", phase="total") == 3


def test_summary_excludes_idle_sweeps():
    tracer = RoundTracer(registry=Registry())
    for i in range(4):
        rec = _rec(i)
        rec.phases_ms = {"total": 10.0}
        tracer._append(rec)
    for i in range(4, 20):  # idle-heavy soak: 16 zero-timing sweeps
        rec = _rec(i, solver_rung=-1)
        rec.phases_ms = {"total": 0.0}
        tracer._append(rec)
    s = tracer.summary("total")
    assert s["rounds"] == 4 and s["idle_rounds"] == 16
    assert s["p50_ms"] == 10.0  # idle sweeps no longer drag p50 to zero
    empty = RoundTracer(registry=Registry())
    assert empty.summary() == {"rounds": 0, "idle_rounds": 0}


# ---------------------------------------------------------------------------
# end-to-end: instrumented service rounds
# ---------------------------------------------------------------------------


def _run_service_rounds(tmp_path, **svc_kw):
    from ksched_tpu.cli import SchedulerService
    from ksched_tpu.cluster import PodEvent, SyntheticClusterAPI

    api = SyntheticClusterAPI()
    svc = SchedulerService(api, backend_name="ref", **svc_kw)
    svc.init_topology(fake_machines=2)
    for i in range(4):
        api.submit_pod(PodEvent(pod_id=f"p{i}"))
    svc.run_round(api.poll_pod_batch(0.05))
    svc.run_round([], solve=False)
    api.close()
    return svc


def test_service_round_timing_is_span_durations(tmp_path):
    with scoped_registry():
        st = SpanTracer().install()
        try:
            svc = _run_service_rounds(tmp_path, span_tracer=st,
                                      tracer=RoundTracer())
        finally:
            st.uninstall()
        by_name = {}
        for ev in st.events():
            by_name.setdefault(ev["name"], []).append(ev)
        # RoundTiming is DERIVED from these spans: the round record's
        # phase values equal the span durations exactly
        rec = svc.tracer.records[0]
        for phase in ("stats", "graph_update", "deltas", "apply"):
            (ev,) = by_name[phase]
            assert rec.phases_ms[phase] == pytest.approx(ev["dur"] / 1e3)
        (round_ev,) = by_name["round"]
        assert rec.phases_ms["total"] == pytest.approx(round_ev["dur"] / 1e3)
        assert round_ev["args"]["parent"] == "service_round"
        # nested solve chain: solve -> ladder -> concrete backend
        solves = by_name["backend_solve"]
        assert {e["args"]["backend"] for e in solves} >= {"ReferenceSolver"}


def test_service_noop_round_trips_flight_dump(tmp_path):
    from ksched_tpu.runtime import ChaosPolicy, FaultInjector

    with scoped_registry() as reg:
        injector = FaultInjector(
            ChaosPolicy(seed=1, solver_total_outage_prob=1.0)
        )
        injector.begin_round(0)
        fl = FlightRecorder(capacity=8, dump_dir=str(tmp_path), registry=reg)
        with pytest.warns(RuntimeWarning):
            svc = _run_service_rounds(
                tmp_path, injector=injector, tracer=RoundTracer(),
                flight=fl,
            )
        assert svc.noop_rounds == 1
        assert len(fl.dumps) == 1
        doc = json.loads(open(fl.dumps[0]).read())
        assert doc["reason"] == "noop_round"
        assert doc["rounds"][0]["record"]["noop_round"] is True
        assert reg.value("ksched_rounds_total", kind="noop") == 1
        assert reg.value("ksched_ladder_exhausted_total") == 1


# ---------------------------------------------------------------------------
# the service loop's own spans, the AutoSolver's, and pod queue wait
# ---------------------------------------------------------------------------

SERVICE_SPANS = (
    "pods_admit", "runnable_scan", "bindings_collect", "bindings_post",
    "round_accounting",
)
AUTO_SPANS = ("collapse_audit", "transport", "flow_reconstruct")


def _auto_service(**svc_kw):
    """A two-machine service whose every round collapses onto the dense
    transport (AutoSolver over the trivial cost model)."""
    from ksched_tpu.cli import SchedulerService
    from ksched_tpu.cluster import SyntheticClusterAPI
    from ksched_tpu.solver.cpu_ref import ReferenceSolver
    from ksched_tpu.solver.graph_collapse import AutoSolver

    api = SyntheticClusterAPI()
    svc = SchedulerService(
        api, max_tasks_per_pu=4, backend=AutoSolver(ReferenceSolver()),
        backend_name="auto", **svc_kw,
    )
    svc.init_topology(fake_machines=2)
    return svc, api


def _pods(prefix, n, waited_s=()):
    import time

    from ksched_tpu.cluster import PodEvent

    now = time.perf_counter()
    return [
        PodEvent(pod_id=f"{prefix}{i}", received_s=now - waited_s[i])
        if i < len(waited_s) else PodEvent(pod_id=f"{prefix}{i}")
        for i in range(n)
    ]


def _by_name(events):
    out = {}
    for ev in events:
        out.setdefault(ev["name"], []).append(ev)
    return out


@pytest.fixture(scope="module")
def traced_auto_rounds():
    """Two solved rounds with pods, a re-solve without pods and an idle
    sweep, under a SpanTracer and a RoundTracer."""
    with scoped_registry() as reg:
        st = SpanTracer().install()
        try:
            svc, api = _auto_service(span_tracer=st, tracer=RoundTracer())
            bound = [
                svc.run_round(_pods("a", 3, waited_s=(0.3, 0.1))),
                svc.run_round(_pods("b", 2)),
                svc.run_round([], solve=True),
                svc.run_round([], solve=False),
            ]
            api.close()
        finally:
            st.uninstall()
        yield svc, st.events(), bound, reg


@pytest.mark.parametrize("name", SERVICE_SPANS + AUTO_SPANS)
def test_new_span_opens_once_per_solved_round_under_its_parent(traced_auto_rounds, name):
    svc, events, bound, _reg = traced_auto_rounds
    assert bound == [3, 2, 0, 0]
    by_name = _by_name(events)
    rounds = sorted(by_name["service_round"], key=lambda e: e["ts"])
    assert len(rounds) == 4
    got = sorted(by_name[name], key=lambda e: e["ts"])
    # the two rounds with pods ran every piece of work once; the re-solve
    # found nothing runnable (no `round`, nothing to post), the sweep
    # only accounts
    want_rounds = {
        "pods_admit": [0, 1, 2], "runnable_scan": [0, 1, 2],
        "bindings_collect": [0, 1, 2], "bindings_post": [0, 1],
        "round_accounting": [0, 1, 2, 3],
    }.get(name, [0, 1])
    assert len(got) == len(want_rounds)
    parent = "backend_solve" if name in AUTO_SPANS else "service_round"
    for ev, r in zip(got, want_rounds):
        assert ev["args"]["parent"] == parent
        outer = rounds[r]
        assert outer["ts"] <= ev["ts"] and ev["ts"] + ev["dur"] <= outer["ts"] + outer["dur"] + 1e-3
        if parent == "service_round":
            assert ev["args"]["parent_sid"] == outer["args"]["sid"]


def test_new_spans_carry_their_args(traced_auto_rounds):
    _svc, events, _bound, _reg = traced_auto_rounds
    by_name = _by_name(events)
    first = {n: min(by_name[n], key=lambda e: e["ts"])["args"] for n in by_name}
    assert first["pods_admit"]["pods"] == 3
    assert first["runnable_scan"]["jobs"] == 1
    assert (first["bindings_collect"]["resident"], first["bindings_collect"]["new"]) == (3, 3)
    assert first["bindings_post"]["n"] == 3
    assert first["collapse_audit"]["collapsed"] is True and "reason" not in first["collapse_audit"]
    assert first["transport"]["rows"] >= 1 and first["transport"]["cols"] == 2
    assert "supersteps" in first["transport"]
    assert first["flow_reconstruct"]["tasks"] == 3
    second = sorted(by_name["bindings_collect"], key=lambda e: e["ts"])[1]["args"]
    assert (second["resident"], second["new"]) == (5, 2)
    # the re-solve without pods found no runnable job
    assert sorted(by_name["runnable_scan"], key=lambda e: e["ts"])[2]["args"]["jobs"] == 0


def test_a_refused_collapse_says_why():
    """The csr branch keeps `backend_solve` minus `collapse_audit` as the
    rung: no transport, no reconstruction, and the audit's reason."""
    from tests.test_scheduler_backends import add_job
    from ksched_tpu.drivers import build_cluster
    from ksched_tpu.solver.cpu_ref import ReferenceSolver
    from ksched_tpu.solver.graph_collapse import AutoSolver

    auto = AutoSolver(ReferenceSolver())
    sched, _rmap, jmap, tmap, _root = build_cluster(
        num_machines=3, num_cores=2, backend=auto, preemption=True,
    )
    with SpanTracer() as st:
        add_job(sched, jmap, tmap, num_tasks=4)
        sched.schedule_all_jobs()  # collapses
        assert auto.last_path == "dense" and auto.last_solve_span.name == "transport"
        add_job(sched, jmap, tmap, num_tasks=4)
        sched.schedule_all_jobs()  # running tasks keep arcs to their leaves: refused
    assert auto.last_path == "csr" and auto.last_solve_span is None
    by_name = _by_name(st.events())
    audits = sorted(by_name["collapse_audit"], key=lambda e: e["ts"])
    assert [e["args"]["collapsed"] for e in audits] == [True, False]
    assert audits[1]["args"]["reason"] == auto.last_refusal != ""
    assert len(by_name["transport"]) == len(by_name["flow_reconstruct"]) == 1


def _raise(*_a, **_k):
    raise RuntimeError("boom")


@pytest.mark.parametrize("name", SERVICE_SPANS + AUTO_SPANS)
def test_new_span_closes_with_the_error_when_its_body_raises(monkeypatch, name):
    from ksched_tpu.runtime import LadderExhausted
    from ksched_tpu.solver import graph_collapse, layered

    svc, api = _auto_service(degrade=name not in AUTO_SPANS)
    svc.run_round(_pods("w", 1))  # a healthy round first: something is resident
    target, attr = {
        "pods_admit": (svc, "_add_pod"),
        "runnable_scan": (svc.scheduler, "_compute_runnable_tasks_for_job"),
        "bindings_collect": (svc.scheduler, "get_task_bindings"),
        "bindings_post": (api, "assign_bindings"),
        "round_accounting": (svc._g_pods, "set"),
        "collapse_audit": (graph_collapse, "try_collapse"),
        "transport": (layered.LayeredTransportSolver, "solve_layered"),
        "flow_reconstruct": (graph_collapse.AutoSolver, "_reconstruct_flow"),
    }[name]
    monkeypatch.setattr(target, attr, _raise)
    with SpanTracer() as st:
        with pytest.raises((RuntimeError, LadderExhausted), match="boom"):
            svc.run_round(_pods("x", 2))
    by_name = _by_name(st.events())
    (ev,) = by_name[name]
    assert ev["args"]["error"] == "RuntimeError: boom"
    # the error closed every span above it too, and the tree is whole
    (outer,) = by_name["service_round"]
    assert "boom" in outer["args"]["error"]
    assert ev["ts"] + ev["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    # the next span on this thread parents at the root again
    with SpanTracer() as st2:
        with span("after"):
            pass
    assert "parent" not in st2.events()[0]["args"]


def test_without_a_tracer_nothing_is_recorded_and_the_round_returns_its_bindings():
    from ksched_tpu.obs.spans import active_tracer

    assert active_tracer() is None
    idle = SpanTracer()  # constructed, never installed
    svc, api = _auto_service()
    pods = _pods("n", 3, waited_s=(0.2,))
    assert svc._queue_wait_ms(pods, 1e9) == (0.0, 0.0)  # nobody reads it: not computed
    assert svc.run_round(pods) == 3
    assert svc.run_round([], solve=False) == 0
    assert idle.events() == [] and idle.total == 0
    assert sorted(api.bindings()) == ["n0", "n1", "n2"]
    assert svc.scheduler.solver.backend.primary.last_solve_span.dur_s > 0  # spans still time


def test_received_s_is_not_part_of_a_pods_identity():
    from ksched_tpu.cluster import PodEvent

    a, b = PodEvent("p", task_class=2, received_s=1.0), PodEvent("p", task_class=2, received_s=9.0)
    assert a == b and hash(a) == hash(b) and len({a, b}) == 1
    assert a != PodEvent("p", task_class=3, received_s=1.0)
    import time

    t0 = time.perf_counter()
    assert t0 <= PodEvent("q").received_s <= time.perf_counter()


def test_queue_wait_is_the_round_start_minus_the_pods_stamps(traced_auto_rounds):
    svc, events, _bound, _reg = traced_auto_rounds
    rounds = sorted(_by_name(events)["service_round"], key=lambda e: e["ts"])
    recs = svc.tracer.records
    assert len(recs) == 4
    # round 0: pods stamped 0.3 s and 0.1 s before `now`, the third at `now`;
    # the round opened a little after, and that little is the same for all
    args = rounds[0]["args"]
    late_ms = args["queue_wait_max_ms"] - 300.0
    assert 0.0 <= late_ms < 250.0
    assert args["queue_wait_ms"] == pytest.approx((400.0 + 3 * late_ms) / 3, abs=1.0)
    for ev, rec in zip(rounds, recs):
        assert rec.queue_wait_ms == ev["args"]["queue_wait_ms"]
        assert rec.queue_wait_max_ms == ev["args"]["queue_wait_max_ms"]
    assert 0.0 < recs[1].queue_wait_ms <= recs[1].queue_wait_max_ms
    # a solve without pods and an idle sweep waited for nobody
    for rec in recs[2:]:
        assert (rec.queue_wait_ms, rec.queue_wait_max_ms) == (0.0, 0.0)


def test_queue_wait_histogram_counts_solved_rounds_that_admitted_pods(traced_auto_rounds):
    svc, _events, _bound, reg = traced_auto_rounds
    assert reg.value("ksched_rounds_total", kind="sched") == 3
    assert reg.value("ksched_rounds_total", kind="idle") == 1
    assert reg.value("ksched_pod_queue_wait_ms") == 2
    assert "ksched_pod_queue_wait_ms_count 2" in render_prometheus(reg)  # on /metricsz


def test_a_redelivered_pod_waited_too():
    svc, api = _auto_service(tracer=RoundTracer())
    svc.run_round(_pods("r", 2))
    again = _pods("r", 1, waited_s=(0.5,))  # same id: the early return of _add_pod
    svc.run_round(again)
    assert len(svc.pod_to_task) == 2
    assert svc.tracer.records[1].queue_wait_max_ms >= 500.0


def test_a_split_round_carries_queue_wait_to_its_record():
    with scoped_registry() as reg:
        svc, api = _auto_service(tracer=RoundTracer())
        with SpanTracer() as st:
            assert svc.dispatch_round(_pods("s", 3, waited_s=(0.25, 0.05))) is True
            assert svc.complete_round() == 3
            svc.dispatch_round([])
            svc.complete_round()
        first, second = svc.tracer.records
        assert 250.0 <= first.queue_wait_max_ms < 500.0
        assert 100.0 <= first.queue_wait_ms < first.queue_wait_max_ms
        assert (second.queue_wait_ms, second.queue_wait_max_ms) == (0.0, 0.0)
        assert reg.value("ksched_pod_queue_wait_ms") == 1
        by_name = _by_name(st.events())
        for name in SERVICE_SPANS + AUTO_SPANS:
            assert name in by_name, name  # the split round opens the same spans
        assert "service_round" not in by_name
