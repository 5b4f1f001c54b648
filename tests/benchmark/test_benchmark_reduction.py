"""The benchmark's yardstick, checked without a chip: the reduction from a
trace and spans to busy, idle and blame (on a small synthetic trace), the
percentile rule (the checks that decide `correct`: test_benchmark_checks.py)."""

import time

import pytest

from benchmarks import observe, roofline, stats
from benchmarks import trace_reduce as tr


# -- intervals, busy union, idle share ---------------------------------------


def test_merge_intervals_unites_overlaps_and_drops_empty_ones():
    got = tr.merge_intervals([(5, 7), (0, 2), (1, 3), (3, 3), (6, 9)])
    assert got == [(0, 3), (5, 9)]


def test_busy_is_the_union_not_the_sum_and_gaps_fill_the_rest():
    ops = [("a", 10, 20), ("b", 20, 20), ("c", 60, 10), ("outside", 200, 50)]
    busy, gaps = tr.busy_and_gaps(ops, 0, 100)
    assert busy == 40  # [10, 40) and [60, 70)
    assert gaps == [(0, 10), (40, 60), (70, 100)]
    assert busy + sum(e - s for s, e in gaps) == 100


def test_an_op_that_straddles_the_window_is_clipped_to_it():
    busy, gaps = tr.busy_and_gaps([("a", -50, 100), ("b", 90, 100)], 0, 100)
    assert busy == 60 and gaps == [(50, 90)]


def test_self_time_takes_nested_ops_out_of_their_parent():
    ops = [("while", 0, 100), ("fusion", 10, 30), ("copy", 50, 20), ("fusion", 200, 5)]
    got = tr.self_times(ops)
    assert got == {"while": 50, "fusion": 35, "copy": 20}


# -- what the host was doing ---------------------------------------------------


def test_flatten_names_each_instant_after_the_deepest_span():
    spans = [("service_round", 0, 100), ("round", 10, 90), ("stats", 10, 30), ("solve", 30, 80),
             ("backend_solve", 40, 70), ("poll_pod_batch", 100, 120)]
    assert tr.flatten_spans(spans) == [
        ("service_round", 0, 10), ("stats", 10, 30), ("solve", 30, 40),
        ("backend_solve", 40, 70), ("solve", 70, 80), ("round", 80, 90),
        ("service_round", 90, 100), ("poll_pod_batch", 100, 120),
    ]


def test_gap_time_goes_to_the_segments_that_overlap_it():
    segs = [("stats", 0, 40), ("graph_update", 40, 100), ("poll_pod_batch", 150, 160)]
    got = tr.attribute_gaps([(20, 60), (90, 155)], segs)
    assert got == {"stats": 20, "graph_update": 30, "poll_pod_batch": 5, "unattributed": 50}


def test_anchor_alignment_gives_offset_and_drift():
    offset, drift = tr.clock_offset_ns([(1_000, 501_000), (9_000, 509_100)])
    assert offset == pytest.approx(-500_050) and drift == pytest.approx(-100)
    with pytest.raises(ValueError):
        tr.clock_offset_ns([])


def test_reduce_trace_on_a_small_synthetic_trace():
    """Two chips, a 1 ms window between the anchors, spans on a
    perf_counter clock that runs 5 s ahead of the trace's."""
    ahead = 5e9
    raw = tr.RawTrace(
        device_ops={
            0: [("while", 100_000, 400_000), ("fusion.1", 150_000, 100_000)],
            1: [("while", 100_000, 200_000)],
        },
        anchors=[(0.0, ahead), (1_000_000.0, ahead + 1_000_000.0)],
    )
    spans = [("service_round", 5.0, 5.0006), ("graph_update", 5.0, 5.0001),
             ("backend_solve", 5.0001, 5.0005), ("poll_pod_batch", 5.0006, 5.002)]
    got = tr.reduce_trace(raw, spans)
    assert got["window_s"] == pytest.approx(1e-3)
    assert got["chips"] == 2 and got["ops"] == 3
    assert got["busy_s"] == pytest.approx((400e-6 + 200e-6) / 2)  # averaged over the chips
    assert 100 * (1 - got["busy_s"] / got["window_s"]) == pytest.approx(70.0)
    assert dict(got["device_ops"]) == pytest.approx({"while": 250e-6, "fusion.1": 50e-6})
    blame = dict(got["idle_gaps"])
    # chip 0 idles [0,100) and [500,1000) us, chip 1 [0,100) and [300,1000) us
    assert blame["graph_update"] == pytest.approx(100e-6)
    assert blame["backend_solve"] == pytest.approx((0 + 200e-6) / 2)
    assert blame["service_round"] == pytest.approx(100e-6)
    assert blame["poll_pod_batch"] == pytest.approx(400e-6)
    assert sum(blame.values()) == pytest.approx(got["window_s"] - got["busy_s"])


def test_a_trace_without_anchors_or_ops_is_refused():
    with pytest.raises(ValueError, match="bench_anchor"):
        tr.reduce_trace(tr.RawTrace(device_ops={0: [("a", 0, 1)]}), [])
    with pytest.raises(ValueError, match="no device op"):
        tr.reduce_trace(tr.RawTrace(anchors=[(0, 0), (1, 1)]), [])


def test_load_xplane_finds_anchors_and_ops_in_a_real_capture(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation(tr.ANCHOR, t_ns=int(time.perf_counter() * 1e9)):
                pass
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    raw = tr.load_xplane(path)
    assert len(raw.anchors) == 2 and raw.anchors[0][0] < raw.anchors[1][0]
    assert raw.device_ops and all(d > 0 for _n, _s, d in raw.device_ops[0])
    offset, drift = tr.clock_offset_ns(raw.anchors)
    assert abs(drift) < 5e6  # the two clocks agree to milliseconds over the capture


# -- spans to rounds ----------------------------------------------------------


def _ev(name, ts_ms, dur_ms, **args):
    args.setdefault("sid", 1)
    return {"name": name, "ts": ts_ms * 1e3, "dur": dur_ms * 1e3, "args": args}


def test_rounds_group_spans_by_service_round_and_skip_synthesized_events():
    events = [
        _ev("service_round", 0, 100, pods=12, solve=True),
        _ev("round", 5, 90), _ev("stats", 5, 10), _ev("graph_update", 15, 20),
        {"name": "superstep", "ts": 40e3, "dur": 1e3, "args": {"step": 0}},
        _ev("service_round", 200, 1, pods=0, solve=False),
    ]
    solved, idle = observe.rounds_from_spans(events)
    assert solved.solved and solved.pods == 12
    assert solved.spans_ms == pytest.approx(
        {"round": 90, "stats": 10, "graph_update": 20, "service_round": 100}
    )
    assert not idle.solved and "superstep" not in solved.spans_ms


def test_reductions_and_nothing_to_reduce():
    assert observe.reduce_values([1, 2, 3, 4], "p50") == 2.5
    assert observe.reduce_values([1, 2, 3], "max") == 3
    assert observe.reduce_values([], "p50") is None
    with pytest.raises(ValueError):
        observe.reduce_values([1], "mode")


# -- the percentile rule ---------------------------------------------------------


@pytest.mark.parametrize("samples, want", [
    (5, 50.0), (19, 50.0), (20, 50.0), (100, 90.0), (199, 90.0), (200, 95.0),
    (999, 95.0), (1000, 99.0), (10_000, 99.9),
])
def test_highest_percentile_with_ten_samples_beyond_it(samples, want):
    assert stats.highest_percentile(samples) == want


# -- roofline -----------------------------------------------------------------------


def test_an_unknown_device_has_no_peaks():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks on file"):
        roofline.peaks("cpu")


def test_byte_functions_follow_their_shapes():
    assert roofline.scan_csr_superstep_bytes(nodes=10, arcs=100) == 12 * 200 + 400 + 160
    assert roofline.transport_superstep_bytes(rows=4, cols=1024) == 16 * 4096 + 20 * 1024 + 48
    assert roofline.transport_cols(1000) == 1024 and roofline.transport_cols(1024) == 1152
