"""Latency diagnostics for the device-resident scheduling round.

Measures, on the ambient platform (real TPU by default, or
JAX_PLATFORMS=cpu):

1. the empty-scan floor — per-iteration cost of a 64-length lax.scan
   doing nothing, which bounds the measurement resolution;
2. the per-call dispatch overhead of a jitted program;
3. the sustained steady-round latency — the bench.py protocol: 64
   data-dependent churn rounds chained in one scan, wall time / 64.

Timing rides the obs span tracer (ksched_tpu/obs/spans.py): every
measured repetition is a span, the reported medians are computed from
the spans' durations, and the whole session exports as Chrome/Perfetto
trace-event JSON (--trace-out) — so the numbers printed and the trace
a human inspects are the same measurement.

Two measurement hazards this tool works around, documented because they
invalidate naive timings on this stack:

- Device-to-host fetches inside a timed chain: all forcing here uses
  jax.block_until_ready (which waits without transferring); nothing is
  fetched until after all timing.
- XLA loop hoisting: a scan body computed from loop-invariant inputs is
  hoisted out of the loop and executes once, so "repeat phase X in a
  scan" times an empty loop. Only the real round chain — where each
  round's state feeds the next — is immune, which is why this tool
  times whole rounds rather than isolated phases.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ksched_tpu.obs.spans import SpanTracer, span  # noqa: E402
from ksched_tpu.scheduler.device_bulk import DeviceBulkCluster  # noqa: E402

R = 64


def _med(fn, name: str, reps: int = 7, **args) -> float:
    """Median wall-ms of `fn` over `reps` calls, each timed as (and
    reported from) one obs span named `name`."""
    ts = []
    for i in range(reps):
        with span(name, rep=i, **args) as sp:
            fn()
        ts.append(sp.dur_s * 1e3)
    return float(np.median(ts))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--trace-out", default="profile_round_trace.json", metavar="PATH",
        help="Chrome/Perfetto trace-event JSON of the measured spans "
        "('' to skip)",
    )
    args = ap.parse_args()

    tracer = SpanTracer().install()
    M, P, S, J, T = 1000, 4, 4, 10, 10_000
    rng = np.random.default_rng(0)
    with span("setup", machines=M, tasks=T):
        dev = DeviceBulkCluster(
            num_machines=M, pus_per_machine=P, slots_per_pu=S, num_jobs=J,
            task_capacity=16384,
        )
        dev.add_tasks(T, rng.integers(0, J, T).astype(np.int32))
    with span("fill_round"):
        fill = dev.round()
        jax.block_until_ready(fill)

    # empty-scan floor + dispatch overhead
    def empty_chunk(x):
        out, _ = lax.scan(lambda c, _: (c + 1, None), x, None, length=R)
        return out

    f_empty = jax.jit(empty_chunk)
    x0 = jnp.int32(0)
    with span("empty_scan_compile"):
        jax.block_until_ready(f_empty(x0))
    empty_ms = _med(
        lambda: jax.block_until_ready(f_empty(x0)), "empty_scan_chunk"
    )

    # the real thing: data-dependent steady rounds (bench protocol)
    churn_n = max(1, T // 100)
    with span("steady_warmup", rounds=R):
        jax.block_until_ready(dev.run_steady_rounds(R, 0.01, churn_n, seed=1))
    stats = []

    def one_chunk():
        s = dev.run_steady_rounds(R, 0.01, churn_n, seed=2 + len(stats))
        jax.block_until_ready(s)
        stats.append(s)

    chunk_ms = _med(one_chunk, "steady_chunk", rounds=R)

    # clock stopped; fetch + verify
    fill_got = dev.fetch_stats(fill)
    assert bool(fill_got["converged"])
    for s in stats:
        assert dev.fetch_stats(s)["converged"].all()
    tracer.uninstall()

    print(f"geometry: T={T} Tcap={dev.Tcap} M={M} P={P} S={S} "
          f"platform={jax.devices()[0].platform}, {R}-round chains")
    print(f"empty scan floor   : {empty_ms / R * 1e3:8.2f} us/iter "
          f"({empty_ms:.3f} ms/call, incl dispatch)")
    print(f"steady round chain : {chunk_ms / R * 1e3:8.2f} us/round "
          f"({chunk_ms:.3f} ms/chunk)")
    if args.trace_out:
        tracer.dump(args.trace_out)
        print(f"trace ({tracer.mark()} spans) -> {args.trace_out}")


if __name__ == "__main__":
    main()
