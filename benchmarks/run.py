#!/usr/bin/env python
"""One cell of the benchmark: pod-to-bind on the served path.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one JSON object as the last line of stdout. The
service is built exactly as `cli.main` builds it (the configuration's
`argv` through `cli.build_arg_parser()` -> `cli.build_service` ->
`init_topology`), and what is timed is `SchedulerService.run` itself, in
this thread. The cluster's users are `client.TrafficDriver`, playing the
plan `traffic.build_plan` drew from `--seed`. A pod's latency runs from
when it was due to the stamp of its Binding.

It refuses to start unless `jax.devices()[0].platform == "tpu"`; the only
way it runs without a chip is `--rehearse-cpu` (the deployment at 1/40 of
its scale, `device` says `cpu`, Pallas under the interpreter).

`--trace 0` prints the cell's end-to-end metrics, taken with every tracer
off. `--trace 1` installs a SpanTracer and a RoundTracer for the whole
run and takes a `jax.profiler` trace of the window's last seconds; it
prints the cell's per-layer metrics, the device's busy time and the
`breakdown`.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import warnings  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the profiler's capture ends this long before the window does
TRACE_MARGIN_S = 0.5
#: where a traced run keeps its capture until it has been reduced
SCRATCH = os.path.join(ROOT, ".bench_out")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="1/40 scale on the CPU, Pallas under the interpreter: "
                    "the only way this runs without a chip")
    return ap.parse_args(argv)


def fail(msg: str) -> "NoReturn":  # noqa: F821
    """No result line, a non-zero exit."""
    print(f"benchmarks/run.py: {msg}", file=sys.stderr)
    sys.exit(1)


class Capture(threading.Thread):
    """Takes the profiler trace of the window's last seconds."""

    def __init__(self, driver, seconds: float, out_dir: str) -> None:
        super().__init__(name="bench-capture", daemon=True)
        self.driver = driver
        self.trace_s = min(6.0, seconds / 2.0)
        self.out_dir = out_dir
        self.error = None
        self.taken = False

    def run(self) -> None:
        import jax

        try:
            self.driver.window_ready.wait()
            if self.driver.error is not None or not self.driver.window1:
                return
            start = self.driver.window1 - TRACE_MARGIN_S - self.trace_s
            time.sleep(max(0.0, start - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            try:
                self._anchor(jax)
                time.sleep(max(0.0, start + self.trace_s - time.perf_counter()))
                self._anchor(jax)
            finally:
                jax.profiler.stop_trace()
            self.taken = True
        except BaseException as e:  # noqa: BLE001 — handed to the main thread
            self.error = e

    @staticmethod
    def _anchor(jax) -> None:
        from benchmarks.trace_reduce import ANCHOR

        with jax.profiler.TraceAnnotation(ANCHOR, t_ns=int(time.perf_counter() * 1e9)):
            pass


def require_device(args, cell, jax) -> list:
    """The devices, or no result and a non-zero exit: there is no CPU
    fallback, and a cell is not run on fewer chips than it asks for."""
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse_cpu:
        if platform != "cpu":
            fail(f"--rehearse-cpu wants the cpu platform, JAX reports {platform!r}")
    elif platform != "tpu":
        fail(f"no chip: jax.devices()[0].platform == {platform!r}, want 'tpu' "
             "(--rehearse-cpu rehearses the cell on the host)")
    elif len(devices) < cell.chips:
        fail(f"{args.workload} needs {cell.chips} chips, JAX sees {len(devices)}")
    return devices


def build_service(config: dict, traced: bool):
    """The service exactly as cli.main builds it from the configuration's
    argv, over the benchmark's own ClusterAPI."""
    from benchmarks.client import BenchClusterAPI
    from ksched_tpu import cli
    from ksched_tpu.obs.spans import SpanTracer
    from ksched_tpu.runtime.trace import RoundTracer

    svc_args = cli.build_arg_parser().parse_args(config["argv"])
    api = BenchClusterAPI(pod_chan_size=svc_args.pod_chan_size)
    span_tracer = SpanTracer(capacity=1 << 21).install() if traced else None
    round_tracer = RoundTracer() if traced else None
    svc = cli.build_service(svc_args, api, tracer=round_tracer, span_tracer=span_tracer)
    api.svc = svc
    svc.init_topology(
        fake_machines=svc_args.num_machines if svc_args.fake_machines else 0,
        node_batch_timeout_s=svc_args.node_batch_timeout,
        cores_per_machine=svc_args.cores_per_machine,
        pus_per_core=svc_args.pus_per_core,
    )
    return svc, api, svc_args, span_tracer, round_tracer


def service_shapes(svc, svc_args, config: dict) -> dict:
    """What the service solved on, as far as it says: `machines` and
    `task_classes` come from the argv and the configuration; `nodes`,
    `arcs` (padded, as solved) and the `path` that answered come from the
    graph path, and are left out for a service that has none."""
    shapes = {
        "machines": int(svc_args.num_machines), "task_classes": int(config["task_classes"]),
    }
    solver = getattr(getattr(svc, "scheduler", None), "solver", None)
    if solver is None:
        return shapes
    rung = solver.backend.primary if svc.ladder is not None else solver.backend
    return {
        "nodes": int(solver.state.n_cap), "arcs": int(solver.state.m_cap), **shapes,
        "path": getattr(rung, "last_path", None) or "csr",
    }


def end_to_end_values(latency_ms, in_window, w0: float) -> dict:
    from benchmarks import stats

    values = {"setup_s": w0 - T_START}
    if latency_ms:
        values["bind_p50_ms"] = stats.percentile(latency_ms, 50)
        values["bind_p95_ms"] = stats.percentile(latency_ms, 95)
    if len(in_window) > 1 and in_window[-1] > w0:
        # Bindings posted in the window, over the window up to the last of
        # them: a closed loop's last wave straddles the window's end, and
        # counting the window whole would make the rate jump by a wave
        # with the smallest change of speed
        values["bound_pods_per_s"] = len(in_window) / (in_window[-1] - w0)
    return values


def reduce_capture(trace_dir: str, span_events, polls, records, clock_skew: float) -> dict:
    """The profiler's capture, reduced; with the solved rounds and the
    supersteps that fell inside it."""
    from benchmarks import trace_reduce

    xplanes = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(xplanes) != 1:
        fail(f"want one .xplane.pb under {trace_dir}, found {len(xplanes)}")
    spans = [
        (e["name"], e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
        for e in span_events if "sid" in e["args"]
    ] + [("poll_pod_batch", t0, t1) for t0, t1, _n in polls]
    trace = trace_reduce.reduce_trace(trace_reduce.load_xplane(xplanes[0]), spans)
    shutil.rmtree(trace_dir, ignore_errors=True)
    t0, t1 = trace["window_perf"]
    inside = [r for r in records if t0 <= r["wall_time"] - clock_skew <= t1]
    trace["rounds"] = sum(1 for r in inside if r["solver_rung"] >= 0)
    trace["supersteps"] = sum(r["solver_work"] for r in inside)
    return trace


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before `import jax`

    import jax

    from benchmarks import correct, observe, spec, stats
    from benchmarks.client import CompileWatch, TrafficDriver
    from benchmarks.traffic import build_plan

    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        fail(str(e))
    devices = require_device(args, cell, jax)

    from ksched_tpu.utils import device_stamp, enable_compile_cache, seed_rng

    # the cache where JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache
    cache_dir = enable_compile_cache()
    # every program goes into the cache, also those that compile in under
    # JAX's default threshold of 1 s: a second run then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t_jax = time.perf_counter()
    compiles = CompileWatch()
    config = cell.config
    if args.rehearse_cpu:
        from ksched_tpu.ops import set_pallas_mode

        set_pallas_mode("interpret")
        config = spec.rehearsal_config(config)
    seed_rng(args.seed)  # task and job ids are drawn from the framework's RNG
    plan = build_plan(cell.traffic, config, args.seed, args.seconds)
    # what a pod carries is the configuration's to say (pods/<name>.py)
    make_pod = spec.pod_maker(cell.pods, config, args.seed)

    # a degradation or a NOOP round warns; here they are counted by the
    # service and decide `correct`, so the warning is only kept for the log
    caught = []
    showwarning = warnings.showwarning
    warnings.showwarning = lambda m, c, f, l, *a, **k: caught.append(f"{c.__name__}: {m}")
    warnings.simplefilter("always", RuntimeWarning)

    svc, api, svc_args, span_tracer, round_tracer = build_service(config, bool(args.trace))
    # the fill: every resident pod is in the channel before the loop
    # starts, so the first poll takes them all and the fill is one round
    if len(plan.resident) > svc_args.pod_chan_size:
        fail(f"--pod-chan-size {svc_args.pod_chan_size} cannot hold the fill "
             f"of {len(plan.resident)} pods")
    api.expect(len(plan.resident))
    for pod_id, task_class in plan.resident:
        api.submit_pod(make_pod(pod_id, task_class))

    t_built = time.perf_counter()
    driver = TrafficDriver(api, plan, args.seconds, compiles, make_pod)
    capture = None
    if args.trace:
        trace_dir = os.path.join(SCRATCH, f"trace-{args.workload}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        capture = Capture(driver, args.seconds, trace_dir)
        capture.start()
    driver.start()
    clock_skew = time.time() - time.perf_counter()  # RoundRecord stamps epoch time
    try:
        svc.run(pod_batch_timeout_s=svc_args.pod_batch_timeout)
    finally:
        api.close()
        driver.join(timeout=60.0)
        if capture is not None:
            capture.join(timeout=120.0)
        if span_tracer is not None:
            span_tracer.uninstall()
        warnings.showwarning = showwarning
    if driver.is_alive() or (capture is not None and capture.is_alive()):
        fail("a benchmark thread did not end")
    if driver.error is not None:
        fail(f"the traffic driver failed: {driver.error!r}")
    if capture is not None and (capture.error is not None or not capture.taken):
        fail(f"the profiler capture failed: {capture.error!r}")

    # -- the window ---------------------------------------------------------
    w0, w1 = driver.window0, driver.window1
    due = driver.due
    first_bind = {p: s[0] for p, s in api.bind_stamps.items()}
    latency_ms = [(first_bind[p] - d) * 1e3 for p, (d, _s) in due.items() if p in first_bind]
    late_ms = [(s - d) * 1e3 for d, s in due.values()]
    failed = sum(1 for p in due if p not in first_bind)
    in_window = sorted(t for t in first_bind.values() if w0 <= t <= w1)
    bind_rounds = len(set(in_window))
    # per Binding stamp (one round's POST), the longest wait it ended
    by_round = {}
    for p, (d, _s) in due.items():
        t = first_bind.get(p)
        if t is not None:
            by_round[t] = max(by_round.get(t, 0.0), (t - d) * 1e3)
    compiles_in_window = compiles.between(w0, w1)

    # -- correct (outside the window) -----------------------------------------
    # one check for every guarantee the configuration states (correct.py)
    ctx = correct.Context(
        config=config, plan=plan, make_pod=make_pod, svc=svc, svc_args=svc_args, due=due,
        bind_stamps=api.bind_stamps, log=api.log,
        completions_refused=api.completions_refused, compiles_in_window=compiles_in_window,
    )
    faults, checks = correct.run_checks(ctx)
    if not latency_ms:
        faults.append("no pod due in the window was bound")

    shapes = service_shapes(svc, svc_args, config)

    # -- metrics ------------------------------------------------------------------
    facts = {}
    trace = None
    if not args.trace:
        values = end_to_end_values(latency_ms, in_window, w0)
        wanted = cell.end_to_end
    else:
        events = [e for e in span_tracer.events() if e["tid"] == threading.get_ident()]
        records = [
            r for r in (vars(r) for r in round_tracer.records)
            if w0 <= r["wall_time"] - clock_skew <= w1
        ]
        trace = reduce_capture(trace_dir, events, api.polls, records, clock_skew)
        obs = observe.Observation(
            device_kind=devices[0].device_kind,
            rounds=observe.rounds_from_spans(
                [e for e in events if w0 <= e["ts"] / 1e6 <= w1]
            ),
            records=records,
            client={"latency_ms": latency_ms, "late_ms": late_ms},
            counters={"compiles_in_window": compiles_in_window},
            shapes=shapes, trace=trace, rehearsal=bool(args.rehearse_cpu),
        )
        values = {}
        for m in cell.per_layer:
            reader = importlib.import_module(f"benchmarks.readers.{m['reader']}")
            value = reader.read(m["params"], obs)
            if value is not None:  # a reader that finds nothing to read
                values[m["name"]] = value
        wanted = cell.per_layer
        facts["trace"] = {
            k: trace[k] for k in ("ops", "rounds", "supersteps", "clock_drift_us", "chips")
        }
        facts["rounds"] = {
            "solved": sum(1 for r in obs.rounds if r.solved),
            # a quiet poll after a completion re-solves (backlog_dirty)
            "solved_without_pods": sum(1 for r in obs.rounds if r.solved and not r.pods),
            "idle_sweeps": sum(1 for r in obs.rounds if not r.solved),
        }

    peak = 0
    for d in devices[: cell.chips]:
        peak = max(peak, int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)))
    device = {**device_stamp(), "memory_peak_bytes": peak}
    result = {
        "correct": not faults,
        "attempted": len(due),
        "failed": failed,
        "metrics": {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in wanted if m["name"] in values
        },
        "device": device,
    }
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"],
        }
    # not read by the driver: what a reader of the run wants to know
    result["facts"] = {
        **ctx.facts,  # what each check compared; the harness's own keys win
        "workload": cell.name, "seed": args.seed, "seconds": args.seconds,
        "rehearsal": bool(args.rehearse_cpu), "faults": faults,
        "window_s": w1 - w0, "bind_rounds": bind_rounds,
        "round_longest_wait_ms": [round(v) for _t, v in sorted(by_round.items())][:256],
        "highest_percentile": stats.highest_percentile(bind_rounds),
        "warmup_extensions": driver.warmup_extensions, "drain_s": driver.drain_s,
        "compile_events": compiles.count(), "compile_cache": dict(compiles.cache),
        "cache_dir": os.path.relpath(cache_dir, ROOT) if cache_dir.startswith(ROOT) else cache_dir,
        "shapes": shapes, "checks": checks, "warnings": caught[:5],
        "run_s": time.perf_counter() - T_START,
        # where set-up went: process start -> JAX up -> service and topology
        # built, channel filled -> fill round bound -> class sweep -> window
        "setup_phases_s": {
            "jax_up": t_jax - T_START, "service_built": t_built - t_jax,
            "fill_round": driver.phase_ends["fill"] - t_built,
            "class_sweep": driver.phase_ends["class_sweep"] - driver.phase_ends["fill"],
            "warmup": w0 - driver.phase_ends["class_sweep"],
        },
        **facts,
    }
    # what each check compared, beside its limit: a record of a run that is
    # not correct keeps the end of stderr
    print("correct: " + json.dumps(
        {"correct": not faults, "checks": checks, "faults": faults, **ctx.facts}
    ), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
