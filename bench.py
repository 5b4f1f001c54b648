#!/usr/bin/env python
"""Benchmark: p50 scheduling-round latency at 10k tasks x 1k machines.

The driver-set north star (BASELINE.json): <10 ms p50 round latency on a
10k-task / 1k-machine flow graph with the trivial cost model, solved by
the JAX/TPU backend. The measurement point mirrors the reference's round
timer around ScheduleAllJobs (cmd/k8sscheduler/scheduler.go:146-150):
one round = stats/capacity refresh + solve + decode + apply.

Prints ONE JSON line:
    {"metric": ..., "value": p50_ms, "unit": "ms", "vs_baseline": ...}

vs_baseline is target_ms / p50_ms (>= 1.0 means the 10 ms target is met).

Steady-state protocol: fill the cluster to ~95%, then each round
complete ~1% of running tasks and admit the same number of new ones —
the incremental re-solve regime Flowlessly's daemon mode serves in the
reference. Use --cold for full from-scratch solves instead.
"""

import argparse
import json
import math
import os
import sys
import time
from typing import Optional

import numpy as np


def _emit_record(out: dict) -> None:
    """Print one JSON record stamped with the device as JAX reports it
    (platform, kind, count): a host reading is machine-distinguishable
    from a device measurement in EVERY record, and the suite parent
    takes its stamp from the first child's record instead of touching
    a backend itself."""
    from ksched_tpu.utils import device_stamp

    out["device"] = device_stamp()
    print(json.dumps(out))


def _solver_work(backend) -> int:
    """Iterations/supersteps the backend spent on its last solve."""
    return getattr(backend, "last_supersteps", None) or getattr(backend, "last_iterations", 0)


#: wall-clock floor under which a timed device region is not believed:
#: a per-round number derived from a sub-floor chunk is treated as an
#: artifact, not a measurement (docs/NOTES.md "Measurement discipline";
#: value kept from the round-5 harness, to be re-derived by measurement)
FLOOR_MS = 110.0
#: minimum wall time of a timed chunk before its per-round quotient is
#: believed. Every timed chunk ends with block_until_ready AND a small
#: scalar fetch (a fetch provably waits for the whole chain), so the
#: fetch round-trip must stay a small fraction of the wall: 2 s keeps
#: it under ~10% at the round-5 harness's measured overheads.
MIN_CHUNK_WALL_MS = 2_000.0
#: leave-one-out relative-error bar above which a latency-model fit is
#: flagged suspect (an outlier chunk wall poisons the lstsq fit; clean
#: fits measure held-out errors well under this)
LOO_SUSPECT_REL_ERR = 0.25


def _round_latency_model(chunk_walls_ms, R, ss_per_chunk, full_per_chunk=None):
    """Per-round latency distribution from chunked measurements.

    The chunk apparatus times R-round chains (no per-round fetches —
    MIN_CHUNK_WALL_MS), so per-round walls are not observed directly.
    But per-ROUND superstep counts ARE recorded, and the round cost
    decomposes as a fixed overhead plus a per-superstep cost:

        wall_chunk = R * t_fixed + kappa * sum(supersteps in chunk)

    Chunks with different superstep totals identify (t_fixed, kappa) by
    least squares; each round's latency is then t_fixed + kappa * ss_i.
    This is the calibrated stand-in for the reference's per-round timer
    (cmd/k8sscheduler/scheduler.go:146-150), which the device path
    cannot carry — and it makes the TAIL visible: a chunk mean hides a
    25k-superstep round inside 16383 cheap ones.

    Returns a dict with the fit and the p50/p99/max of the modeled
    per-round latency. Fit degeneracies (all-equal superstep totals, or
    a negative component from noise) clamp to the chunk-mean model —
    flagged via "fit" so readers know which regime produced the number.

    OUT-OF-SAMPLE CHECK (VERDICT r3 #3): with >= 3 chunks, each chunk's
    wall is predicted by a model fit on the OTHERS (leave-one-out); the
    relative errors ride along as loo_rel_err_mean/max and
    "fit_suspect" flags fits whose held-out prediction misses by more
    than LOO_SUSPECT_REL_ERR — replacing the eyeball-the-kappa
    discipline docs/NOTES.md used for series with outlier chunks.

    TWO-REGIME MIXTURE (stability-aware preemption): when
    full_per_chunk marks which rounds ran the full tiered re-solve,
    incremental and full rounds get separate per-superstep
    coefficients (the tiered solve's superstep is ~10x the fused
    kernel's) — wall = R*t_fixed + k_i*Σss_incr + k_f*Σss_full — and
    each round's latency maps through its own regime's line.
    """
    walls = np.asarray(chunk_walls_ms, np.float64)
    ss_cat = np.concatenate(ss_per_chunk).astype(np.float64)
    mixture = (
        full_per_chunk is not None
        and any(np.any(f) for f in full_per_chunk)
        and not all(np.all(f) for f in full_per_chunk)
    )
    if mixture:
        full_cat = np.concatenate(full_per_chunk).astype(bool)
        ss_i = np.array([
            float(np.sum(np.asarray(s)[~np.asarray(f, bool)]))
            for s, f in zip(ss_per_chunk, full_per_chunk)
        ])
        ss_f = np.array([
            float(np.sum(np.asarray(s)[np.asarray(f, bool)]))
            for s, f in zip(ss_per_chunk, full_per_chunk)
        ])
    else:
        ss_i = np.array([float(np.sum(s)) for s in ss_per_chunk])
        ss_f = np.zeros_like(ss_i)

    def _fit(w, si, sf):
        """(t_fixed, k_i, k_f, fit_kind) for chunk walls w. The
        2-regime fit needs >= 4 chunks: with exactly 3 the 3-parameter
        system is exactly determined (zero residual df) and fits noise
        — a 3-chunk suite run produced k_incr > k_full, which is
        nonsense; the merged-slope model with its LOO check is the
        honest fallback there."""
        if mixture and len(w) >= 4 and np.ptp(si) > 0 and np.ptp(sf) > 0:
            A = np.stack([np.full_like(si, R), si, sf], axis=1)
            (tf, ki, kf), *_ = np.linalg.lstsq(A, w, rcond=None)
            if tf >= 0 and ki >= 0 and kf >= 0:
                return float(tf), float(ki), float(kf), "lstsq-2regime"
            # degenerate mixture fit: fall through to the single-slope
            # model on combined supersteps
        st = si + sf
        if len(w) >= 2 and np.ptp(st) > 0:
            A = np.stack([np.full_like(st, R), st], axis=1)
            (tf, kp), *_ = np.linalg.lstsq(A, w, rcond=None)
            if kp >= 0 and tf >= 0:
                return float(tf), float(kp), float(kp), "lstsq"
            if kp >= 0:
                # tf < 0: supersteps dominate so strongly the intercept
                # went negative from noise — refit through the origin
                kp = float(np.sum(w * st) / np.sum(st * st))
                return 0.0, kp, kp, "origin"
        # all-equal superstep totals (or a single chunk): all
        # information is in the mean
        m = float(w.mean() / R)
        return m, 0.0, 0.0, "chunk-mean"

    t_fixed, k_i, k_f, fit = _fit(walls, ss_i, ss_f)
    if mixture:
        lat = t_fixed + np.where(full_cat, k_f, k_i) * ss_cat
    else:
        lat = t_fixed + k_i * ss_cat
    out = {
        "fit": fit,
        "fixed_ms": round(t_fixed, 4),
        "per_superstep_us": round(k_i * 1e3, 4),
        "p50_ms": round(float(np.percentile(lat, 50)), 4),
        "p99_ms": round(float(np.percentile(lat, 99)), 4),
        "max_ms": round(float(lat.max()), 4),
    }
    if mixture:
        out["per_superstep_us_full"] = round(k_f * 1e3, 4)
    if len(walls) >= 3:
        # a fold only counts when its subfit ran in the SAME regime as
        # the full fit — a 4-chunk mixture run's 3-chunk subfits can
        # only do the merged-slope model, and judging the 2-regime fit
        # by a merged-slope prediction would flag clean fits (hybrid
        # configs therefore measure 5 chunks: 4-chunk subfits keep the
        # 2-regime form and the LOO check stays live)
        errs = []
        for i in range(len(walls)):
            keep = np.arange(len(walls)) != i
            tf_i, ki_i, kf_i, kind_i = _fit(walls[keep], ss_i[keep], ss_f[keep])
            if kind_i != fit:
                continue
            pred = R * tf_i + ki_i * ss_i[i] + kf_i * ss_f[i]
            errs.append(abs(pred - walls[i]) / max(walls[i], 1e-9))
        if errs:
            out["loo_rel_err_mean"] = round(float(np.mean(errs)), 4)
            out["loo_rel_err_max"] = round(float(np.max(errs)), 4)
            out["fit_suspect"] = bool(np.max(errs) > LOO_SUSPECT_REL_ERR)
    return out


def _device_bench(
    *,
    tasks: int,
    machines: int,
    pus: int,
    slots: int,
    jobs: int,
    churn: float,
    rounds: int,
    chunk: int,
    num_task_classes: int = 1,
    class_cost_fn=None,
    supersteps=None,
    unsched_cost: int = 5,
    ec_cost: int = 2,
    decode_width=None,
    num_groups: int = 0,
    group_setup=None,  # (cluster, rng) -> per-task group ids for the fill
    refine_waves: int = 8,  # matches the DeviceBulkCluster default
    alpha: int = 8,
    preemption: bool = False,
    continuation_discount: int = 1,
    preempt_every: int = 1,
    preempt_drift: int = 0,
    preempt_global_every: int = 0,
    preempt_scope_tau: int = 1,
    preempt_scoped_width=None,
    preempt_incr_budget=None,
    label: str = "trivial cost model",
    verbose: bool = False,
) -> dict:
    """Measure sustained p50 round latency on the device-resident path.

    The timed region per round matches the reference's (everything
    inside ScheduleAllJobs: stats refresh, graph update, solve, decode,
    delta apply — cmd/k8sscheduler/scheduler.go:146-150); binding
    readback happens outside it, as the reference's AssignBinding does.
    Rounds within a chunk are data-dependent (round N's completions draw
    from round N-1's placements), so a chunk is R genuinely sequential
    rounds; its wall time divided by R is the sustained round latency.
    Completion of the whole chain is forced INSIDE the timed region by
    block_until_ready plus a tiny scalar fetch (see MIN_CHUNK_WALL_MS);
    chunk walls are sized to keep the fetch round-trip under ~10% of
    the reading, erring conservative. The bulk stats transfer is still
    deferred until after all timing; convergence of every round is
    asserted from the deferred fetches once the clock stops."""
    import jax
    from ksched_tpu.scheduler.device_bulk import DeviceBulkCluster
    from ksched_tpu.utils import next_pow2

    rng = np.random.default_rng(0)
    dev = DeviceBulkCluster(
        num_machines=machines,
        pus_per_machine=pus,
        slots_per_pu=slots,
        num_jobs=jobs,
        num_task_classes=num_task_classes,
        task_capacity=next_pow2(tasks + 4096),
        class_cost_fn=class_cost_fn,
        supersteps=supersteps,
        unsched_cost=unsched_cost,
        ec_cost=ec_cost,
        decode_width=decode_width,
        num_groups=num_groups,
        refine_waves=refine_waves,
        alpha=alpha,
        preemption=preemption,
        continuation_discount=continuation_discount,
        preempt_every=preempt_every,
        preempt_drift=preempt_drift,
        preempt_global_every=preempt_global_every,
        preempt_scope_tau=preempt_scope_tau,
        preempt_scoped_width=preempt_scoped_width,
        preempt_incr_budget=preempt_incr_budget,
    )
    devices = jax.devices()
    churn_n = max(1, int(tasks * churn))

    init_groups = None if group_setup is None else group_setup(dev, rng)
    dev.add_tasks(
        tasks,
        rng.integers(0, jobs, tasks).astype(np.int32),
        rng.integers(0, num_task_classes, tasks).astype(np.int32),
        groups=init_groups,
    )
    t0 = time.perf_counter()
    fill = dev.round()
    jax.block_until_ready(fill)
    fill_s = time.perf_counter() - t0

    # --- chunk sizing ------------------------------------------------
    # A chunk of R data-dependent rounds is timed as one unit, closed
    # by block_until_ready and a scalar fetch (MIN_CHUNK_WALL_MS). The
    # wall must clear the bar before the per-round quotient is
    # believed; R is not scaled proportionally from sub-bar walls — it
    # grows geometrically until a probe chunk clears the bar. On the
    # CPU platform the bar is 0 and chunking is amortization.
    platform = devices[0].platform
    min_wall_ms = MIN_CHUNK_WALL_MS if platform != "cpu" else 0.0

    def timed_chunk(R, seed):
        """One timed chunk: dispatch R rounds, wait via block + a tiny
        scalar fetch (the true barrier). Returns (wall_ms, stats)."""
        t0 = time.perf_counter()
        stats = dev.run_steady_rounds(R, churn, churn_n, seed=seed)
        jax.block_until_ready(stats)
        np.asarray(jax.device_get(stats["live"][-1]))
        return (time.perf_counter() - t0) * 1e3, stats

    # The probe must clear the bar with a 4x margin: round latency can
    # vary several-fold between chunks (e.g. locality rounds alternate
    # between trivial and contended solves), and a chunk whose wall
    # falls below the bar is rejected — so R is sized off the probe
    # with headroom for faster-than-probe chunks.
    R = min(chunk, rounds)
    # hybrid-preempt configs grow R gently (2x, not 8x): their p99
    # claim rides the 2-regime latency fit, and oversized chunks
    # average the per-chunk superstep totals into near-collinearity —
    # two suite-scale runs at R=16384 produced degenerate (origin)
    # fits where R=2048 identified both slopes cleanly. Smaller
    # chunks = more relative superstep variance = a conditioned fit,
    # at the price of one extra probe compile.
    hybrid_cfg = preemption and (preempt_every > 1 or preempt_drift > 0)
    grow = 2 if hybrid_cfg else 8
    while True:
        # warm the scan executable for this R (num_rounds is static)
        jax.block_until_ready(dev.run_steady_rounds(R, churn, churn_n, seed=1))
        probe_ms, _ = timed_chunk(R, seed=1)
        if probe_ms >= 4 * min_wall_ms or R >= (1 << 20):
            break
        if verbose:
            print(
                f"# probe chunk R={R}: wall {probe_ms:.1f} ms under the "
                f"{4 * min_wall_ms:.0f} ms probe bar - growing R",
                file=sys.stderr,
            )
        R *= grow
    if probe_ms < min_wall_ms:
        raise RuntimeError(
            f"chunk wall {probe_ms:.2f} ms below {min_wall_ms:.0f} ms at "
            f"R={R}: per-round latency unmeasurable at this chunk size"
        )

    while True:
        # a measured chunk can still undercut the bar (heavy round-to-
        # round variance, or a sub-bar reading the probe's 4x margin
        # missed): retry it once, then GROW R and restart measurement
        # rather than reporting a number the bar does not cover
        # >= 3 chunks for the p50; hybrid-preempt configs take 5 so
        # the TWO-REGIME latency fit is over-determined (3 params) AND
        # its leave-one-out folds (4-chunk subfits) can run the same
        # regime — at 3 chunks the mixture fit is exactly determined
        # and fits noise (a suite run produced k_incr > k_full);
        # 7 chunks once the gentle-growth probe keeps them small
        chunks = max(7 if hybrid_cfg else 3, -(-rounds // R))
        per_round_ms = []
        chunk_walls_ms = []
        chunk_stats = []
        grown = False
        for rep in range(chunks):
            wall_ms, stats = timed_chunk(R, seed=2 + rep)
            if wall_ms < min_wall_ms:
                wall_ms, stats = timed_chunk(R, seed=100 + rep)
            if wall_ms < min_wall_ms:
                if R >= (1 << 20):
                    raise RuntimeError(
                        f"chunk {rep} wall {wall_ms:.2f} ms below the "
                        f"{min_wall_ms:.0f} ms bar at R={R} - rejecting "
                        "the measurement"
                    )
                if verbose:
                    print(
                        f"# chunk {rep} wall {wall_ms:.1f} ms under the "
                        f"{min_wall_ms:.0f} ms bar - growing R from {R}",
                        file=sys.stderr,
                    )
                R *= 4
                # warm the new-R executable AND drain it with the same
                # scalar-fetch barrier as timed chunks: block_until_ready
                # alone can return early here, and an undrained warm-up
                # chain would bleed into the restarted rep-0 wall
                warm = dev.run_steady_rounds(R, churn, churn_n, seed=1)
                jax.block_until_ready(warm)
                np.asarray(jax.device_get(warm["live"][-1]))
                grown = True
                break
            chunk_walls_ms.append(round(wall_ms, 1))
            per_round_ms.append(wall_ms / R)
            chunk_stats.append(stats)
        if not grown:
            break

    # Clock stopped — now fetch and verify everything.
    fill_got = dev.fetch_stats(fill)
    assert bool(fill_got["converged"]), "fill round did not converge"
    if verbose:
        print(
            f"# fill: placed {int(fill_got['placed'])}/{tasks} in "
            f"{fill_s:.2f}s (incl compile), "
            f"unsched={int(fill_got['unscheduled'])}",
            file=sys.stderr,
        )
    ss_all, full_all, glob_all, placed_all, live_last = [], [], [], [], 0
    drift_all, esc_all = [], []
    for rep, stats in enumerate(chunk_stats):
        got = dev.fetch_stats(stats)
        assert got["converged"].all(), "a steady round did not converge"
        ss = got.get("supersteps")
        if ss is not None:
            ss_all.append(np.asarray(ss))
        if "full_round" in got:
            full_all.append(np.asarray(got["full_round"]))
        if "global_round" in got:
            glob_all.append(np.asarray(got["global_round"]))
        if "census_drift" in got:
            drift_all.append(np.asarray(got["census_drift"]))
        if "escalated_round" in got:
            esc_all.append(np.asarray(got["escalated_round"]))
        placed_all.append(np.asarray(got["placed"]))
        live_last = int(got["live"][-1])
        if verbose:
            print(
                f"# chunk {rep}: {per_round_ms[rep]:.3f} ms/round x {R} rounds "
                f"(wall {chunk_walls_ms[rep]:.0f} ms), "
                f"placed/round mean {got['placed'].mean():.1f}, "
                f"live {int(got['live'][-1])}"
                + (f", supersteps mean {ss.mean():.0f} max {int(ss.max())}"
                   if ss is not None else ""),
                file=sys.stderr,
            )

    p50 = float(np.percentile(per_round_ms, 50))
    target_ms = 10.0
    detail = {
        "rounds_per_chunk": R,
        "chunks_wall_ms": chunk_walls_ms,
        "floor_bar_ms": round(min_wall_ms, 1),
        "placed_per_round_mean": round(float(np.mean(placed_all)), 2),
        "live_final": live_last,
    }
    if ss_all:
        ss_cat = np.concatenate(ss_all)
        # solver-interior telemetry for --obs-out: the fused device
        # rounds expose per-round superstep counts through fetch_stats;
        # publish them AFTER the clock stopped (hot loop untouched)
        from ksched_tpu.obs import soltel

        soltel.publish_round_supersteps(ss_cat, backend=f"device/{platform}")
        detail["supersteps_p50"] = int(np.percentile(ss_cat, 50))
        detail["supersteps_p99"] = int(np.percentile(ss_cat, 99))
        detail["supersteps_max"] = int(ss_cat.max())
        detail["latency_model"] = _round_latency_model(
            np.array(chunk_walls_ms), R, ss_all,
            full_per_chunk=full_all or None,
        )
        if full_all:
            detail["full_rounds"] = int(np.concatenate(full_all).sum())
            detail["rounds_total"] = int(sum(len(f) for f in full_all))
        # forensic anchor for the max tail (VERDICT r4 #5): the top
        # rounds by superstep count, each with its tier and context,
        # so an artifact reader can see WHICH regime the monsters live
        # in without a re-run
        k = min(8, len(ss_cat))
        top = np.argsort(ss_cat)[-k:][::-1]
        fcat_t = np.concatenate(full_all).astype(bool) if full_all else None
        gcat_t = np.concatenate(glob_all).astype(bool) if glob_all else None
        dcat_t = np.concatenate(drift_all) if drift_all else None
        ecat_t = np.concatenate(esc_all).astype(bool) if esc_all else None
        detail["top_rounds"] = [
            {
                "round": int(i),
                "supersteps": int(ss_cat[i]),
                **(
                    {
                        "tier": (
                            "escalated"
                            if ecat_t is not None and ecat_t[i]
                            else "global"
                            if gcat_t is not None and gcat_t[i]
                            else "scoped" if fcat_t[i] else "incremental"
                        )
                    }
                    if fcat_t is not None else {}
                ),
                **(
                    {"census_drift": int(dcat_t[i])}
                    if dcat_t is not None else {}
                ),
            }
            for i in top
        ]
        if esc_all:
            detail["escalated_rounds"] = int(np.concatenate(esc_all).sum())
        if glob_all and preempt_global_every > 0:
            detail["global_rounds"] = int(np.concatenate(glob_all).sum())
            # scoped-regime evidence: the p99 claim rests on scoped
            # re-solves being cheap — record their superstep spread
            # separately from the rare global rounds
            gcat = np.concatenate(glob_all).astype(bool)
            fcat = np.concatenate(full_all).astype(bool)
            scat = ss_cat
            scoped = fcat & ~gcat
            if scoped.any():
                detail["supersteps_scoped_p99"] = int(
                    np.percentile(scat[scoped], 99)
                )
                detail["supersteps_scoped_max"] = int(scat[scoped].max())
            if gcat.any():
                detail["supersteps_global_max"] = int(scat[gcat].max())
    return {
        "metric": (
            f"p50 scheduling-round latency, {tasks} tasks x "
            f"{machines} machines, {label}, "
            f"{churn:.0%} churn, device-resident rounds "
            f"({R}-round chains), backend=device/{platform}"
        ),
        "value": round(p50, 4),
        "unit": "ms",
        "vs_baseline": round(target_ms / p50, 3),
        "detail": detail,
    }



def parse_overrides(pairs, allowed):
    """--override K=V pairs -> dict with int/float coercion; rejects
    unknown keys so a typo'd ablation cannot silently no-op."""
    ov = {}
    for kv in pairs or []:
        k, sep, v = kv.partition("=")
        if not sep:
            raise SystemExit(f"--override wants K=V, got {kv!r}")
        try:
            ov[k] = int(v)
        except ValueError:
            try:
                # scientific notation ("rate=1e5") and decimals land
                # here; malformed values exit cleanly, not a traceback
                ov[k] = float(v)
            except ValueError:
                raise SystemExit(
                    f"--override wants a numeric value, got {kv!r}"
                ) from None
            if not math.isfinite(ov[k]):
                raise SystemExit(
                    f"--override wants a finite value, got {kv!r}"
                )
    unknown = set(ov) - set(allowed)
    if unknown:
        raise SystemExit(f"unknown --override keys: {sorted(unknown)}")
    return ov


def run_device_bench(args) -> None:
    out = _device_bench(
        tasks=args.tasks,
        machines=args.machines,
        pus=args.pus,
        slots=args.slots,
        jobs=args.jobs,
        churn=args.churn,
        rounds=args.rounds,
        chunk=args.chunk,
        verbose=args.verbose,
    )
    if args.tasks == 10_000 and args.machines == 1_000:
        # the headline config is class-degenerate by construction (the
        # trivial model), so its rounds take the exact closed form with
        # zero solver iterations — say so, and point at the configs
        # that exercise the iterative solver (VERDICT r2 weak #6)
        out["detail"]["note"] = (
            "trivial model is class-degenerate: rounds take the exact "
            "closed form (supersteps 0); iterative-solver flagships are "
            "quincy10k / coco50k / whare-hetero in --suite"
        )
    _emit_record(out)


def _churn_pipeline_bench(
    tasks: int = 10_000,
    machines: int = 1_000,
    rounds: int = 24,
    churn: float = 0.01,
    restart_budget: int = 64,
    cold_control: bool = True,
    warmup: int = 6,
    verbose: bool = False,
) -> dict:
    """The steady-state churn benchmark for the device-resident round
    pipeline (event path: FlowScheduler + PlacementSolver + JaxSolver).

    Three arms run the IDENTICAL seeded scenario — same graph
    evolution, same solver policy (slot-stable plan + dirty-frontier
    price refit, budgeted restart escape as backstop), so placements
    are bit-identical BY CONSTRUCTION and the bench asserts it every
    round. The arms differ only in how the folded problem reaches the
    solver:

    - ``full_rebuild``: the r9 status-quo export — every round
      re-copies/refolds ALL host arrays (problem() cache bypassed) and
      re-uploads every one of them (fresh device_put);
    - ``delta_scatter``: the host-side delta path — the journal
      scatters into the host arrays and the problem() cache rebuilds
      only dirty groups; the device still receives full uploads;
    - ``device_resident``: persistent device buffers — only packed
      delta records cross the host/device boundary (the problem-delta
      scatter AND the plan-row scatter), warm flow + potentials stay
      device-resident.

    Two baseline measurements attribute the win: ``reference`` runs
    the full_rebuild export with the r9 solver defaults (legacy plan,
    no warm potentials, no restart escape) and ``r11_policy`` runs the
    device-resident export with the r11 policy (legacy argsort plan
    rebuilt per endpoint change, warm prices OFF, budgeted restart
    escape as the price-war band-aid) — the 407 ms/747-supersteps p50
    path this change retires. ``cold_control`` additionally measures
    the canonical cold solve (zero flow, full cost-scaling from
    eps = max|cost|·n — the complete() fallback) on the final round's
    problem, the baseline for the warm-supersteps claim.

    The arms are INTERLEAVED round-robin, one round each per logical
    round: ambient machine drift (the dominant noise on CPU, measured
    ~±25% over a multi-minute sequential run) then hits every arm
    equally, so the cross-arm comparison is paired rather than
    confounded by whichever arm ran during a slow window.
    """
    import jax

    from ksched_tpu.drivers import add_job, build_cluster
    from ksched_tpu.drivers.synthetic import add_task_to_job
    from ksched_tpu.graph.device_export import DeviceResidentState
    from ksched_tpu.obs import DeviceProfiler, set_profiler
    from ksched_tpu.obs.devprof import problem_nbytes
    from ksched_tpu.obs.metrics import Registry
    from ksched_tpu.obs.soltel import SolverStallError
    from ksched_tpu.solver.jax_solver import JaxSolver
    from ksched_tpu.utils import seed_rng

    k = max(1, int(tasks * churn))
    # the arms sharing the new default policy — placements must match
    # bit-for-bit across these, every round
    _PARITY_ARMS = ("full_rebuild", "delta_scatter", "device_resident")
    # (label, export, restart_budget, r11-policy?) — r11 policy =
    # legacy argsort plan + warm prices OFF (the defaults before the
    # slot-stable plan and the dirty-frontier refit landed)
    arm_specs = (
        ("reference", "full", None, True),
        ("r11_policy", "resident", restart_budget, True),
        ("full_rebuild", "full", restart_budget, False),
        ("delta_scatter", "cache", restart_budget, False),
        ("device_resident", "resident", restart_budget, False),
    )
    out_arms = {}
    placements_by_round = {}

    class _Arm:
        def __init__(self, label, export, budget, r11_policy):
            self.label = label
            self.export = export
            # the reference (status-quo) arm's warm attempts degenerate
            # cumulatively on this workload — by ~round 27 even the
            # 50k-superstep cost-scaling fallback stalls (the failure
            # mode the budgeted restart escape removes). Cap its rounds
            # and record a stall as DATA, not a crash.
            self.arm_rounds = min(rounds, 12) if label == "reference" else rounds
            self.reg = Registry()
            self.prof = DeviceProfiler(registry=self.reg)
            set_profiler(self.prof)
            seed_rng(7)
            self.solver = JaxSolver(
                restart_budget=budget,
                slot_stable=not r11_policy,
                warm_potentials=not r11_policy,
                journal_scoped_warm=not r11_policy,
            )
            (
                self.sched, self.rmap, self.jmap, self.tmap, self.root,
            ) = build_cluster(
                num_machines=machines, num_cores=1, pus_per_core=4,
                max_tasks_per_pu=4, backend=self.solver,
            )
            if export == "resident":
                self.sched.solver.device_resident = True
                self.sched.solver.resident = DeviceResidentState(
                    self.sched.solver.state
                )
            self.job_id = add_job(self.sched, self.jmap, self.tmap, num_tasks=tasks)
            t0 = time.perf_counter()
            self.sched.schedule_all_jobs()
            self.fill_s = time.perf_counter() - t0
            self.fill_ss = self.solver.last_supersteps
            self.rng = np.random.default_rng(123)
            self.lat_ms = []
            self.ss_hist = []
            self.h2d_mark = (0.0, 0.0)
            self.plan_kinds = {}  # resident plan sync kinds, post-warmup
            self.plan_bytes = 0
            self.scope_counts = {}  # journal-scoped warm decisions
            self.stalled_at = None
            # task/job ids come from the process-global seeded RNG
            # (utils.seed_rng); interleaved arms must each see their
            # OWN continuation of the seed-7 stream or ids (and thus
            # placements) diverge across arms — snapshot the stream
            # here and swap it in around every round
            from ksched_tpu.utils.ids import rng as global_rng

            self._global_rng = global_rng
            self._rng_state = global_rng().getstate()

        def h2d(self, kind):
            return self.reg.value("ksched_h2d_bytes_total", kind=kind)

        def drive_round(self, r):
            set_profiler(self.prof)
            self._global_rng().setstate(self._rng_state)
            if r == warmup:
                # steady state reached: pow2 record buckets and the
                # budgeted-attempt executables are compiled; start the
                # clock and the byte accounting
                self.h2d_mark = (self.h2d("full_build"), self.h2d("delta"))
            sched, tmap = self.sched, self.tmap
            bound = sorted(sched.task_bindings.items())
            idx = sorted(
                int(x) for x in self.rng.choice(len(bound), k, replace=False)
            )
            for i in reversed(idx):
                sched.handle_task_completion(tmap.find(bound[i][0]))
            for _ in range(k):
                add_task_to_job(self.job_id, self.jmap, tmap)
            sched.add_job(self.jmap.find(self.job_id))
            # the adds were this round's only global-RNG consumers:
            # park the arm's stream for its next round
            self._rng_state = self._global_rng().getstate()
            if self.export == "full":
                # status-quo export: bypass the problem() cache so
                # every round re-copies and refolds all arrays
                st = sched.solver.state
                st._cache_nodes_ok = st._cache_arcs_ok = False
            t0 = time.perf_counter()
            try:
                sched.schedule_all_jobs()
            except SolverStallError as e:
                self.stalled_at = r
                print(
                    f"# churn[{self.label}] STALLED at round {r}: {e}",
                    file=sys.stderr,
                )
                return
            wall_ms = (time.perf_counter() - t0) * 1e3
            if self.label in _PARITY_ARMS:
                snap = {
                    tmap.find(t).name: rid
                    for t, rid in sched.task_bindings.items()
                }
                placements_by_round.setdefault(r, {})[self.label] = snap
            if r < warmup:
                return
            self.lat_ms.append(wall_ms)
            self.ss_hist.append(self.solver.last_supersteps)
            scope = self.solver.last_warm_scope
            self.scope_counts[scope] = self.scope_counts.get(scope, 0) + 1
            if self.export == "resident":
                res = sched.solver.resident
                kind = res.last_plan_kind
                self.plan_kinds[kind] = self.plan_kinds.get(kind, 0) + 1
                self.plan_bytes += res.last_plan_bytes
            if verbose:
                print(
                    f"# churn[{self.label}] round {r}: {wall_ms:.1f}ms "
                    f"ss={self.ss_hist[-1]}",
                    file=sys.stderr,
                )

    try:
        arm_objs = [_Arm(*spec) for spec in arm_specs]
        for r in range(warmup + rounds):
            for a in arm_objs:
                if a.stalled_at is not None or r >= warmup + a.arm_rounds:
                    continue
                a.drive_round(r)
    finally:
        set_profiler(None)

    for a in arm_objs:
        label, export = a.label, a.export
        sched, solver = a.sched, a.solver
        lat_ms, ss_hist, stalled_at = a.lat_ms, a.ss_hist, a.stalled_at
        full_b, delta_b = a.h2d("full_build"), a.h2d("delta")
        h2d_mark = a.h2d_mark
        prob = sched.solver.state.problem()
        measured = max(len(lat_ms), 1)
        arm = {
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 3) if lat_ms else None,
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 3) if lat_ms else None,
            "mean_ms": round(float(np.mean(lat_ms)), 3) if lat_ms else None,
            "fill_s": round(a.fill_s, 2),
            "fill_supersteps": int(a.fill_ss),
            "supersteps_p50": int(np.percentile(ss_hist, 50)) if ss_hist else None,
            "supersteps_p99": int(np.percentile(ss_hist, 99)) if ss_hist else None,
            "supersteps_max": int(max(ss_hist)) if ss_hist else None,
            "measured_rounds": len(lat_ms),
            "warm_scope_rounds": dict(a.scope_counts),
            "h2d_full_bytes": int(full_b - h2d_mark[0]),
            "h2d_delta_bytes": int(delta_b - h2d_mark[1]),
            "h2d_delta_bytes_per_round": int((delta_b - h2d_mark[1]) / measured),
            "problem_nbytes": int(problem_nbytes(prob)),
        }
        if stalled_at is not None:
            arm["stalled_at_round"] = stalled_at
            arm["stall"] = (
                "cost-scaling fallback exceeded max_supersteps — the "
                "unbudgeted warm path degenerates cumulatively; the "
                "restart_budget arms do not exhibit this"
            )
        if export == "resident":
            sched.solver.resident.parity_check()
            sched.solver.resident.plan_parity_check()
            arm["h2d_accounting"] = "exact (packed-record nbytes)"
            # for the resident arm the counted delta bytes ARE
            # the real per-round upload
            arm["h2d_real_upload_per_round"] = arm["h2d_delta_bytes_per_round"]
            arm["delta_records_last"] = int(
                sched.solver.resident.last_arc_records
                + sched.solver.resident.last_node_records
            )
            # slot-stable plan maintenance: sync kinds per measured
            # round (clean = no endpoint churn, delta = packed plan
            # records through the scatter, rebuild = layout rebuilt —
            # full_build / bucket growth / region overflow only) and
            # the plan bytes that rode the boundary post-warmup
            arm["plan_sync_kinds"] = dict(a.plan_kinds)
            arm["plan_bytes_total"] = int(a.plan_bytes)
            arm["plan_bytes_per_round"] = int(a.plan_bytes / measured)
            arm["plan_layout_rebuilds"] = int(
                sched.solver.state.plan.layout_rebuilds
            )
            arm["plan_region_overflows"] = int(
                sched.solver.state.plan.region_overflows
            )
            arm["plan_region_relocations"] = int(
                sched.solver.state.plan.region_relocations
            )
        else:
            arm["h2d_accounting"] = (
                "journal estimate; device uploads remain full arrays"
            )
            # non-resident arms re-device_put the five solver
            # arrays (cap/cost/excess/flow0 + the int32 casts)
            # every round: the real upload is graph-sized
            arm["h2d_real_upload_per_round"] = int(
                prob.cap.nbytes + prob.cost.nbytes
                + prob.excess.astype(np.int32).nbytes
                + prob.cap.nbytes  # flow0
            )
        if cold_control and label == "device_resident":
            # canonical cold solve on the final problem: zero
            # flow, full cost-scaling (the complete() fallback)
            from ksched_tpu.solver.jax_solver import _solve_mcmf

            n = prob.num_nodes
            m = len(prob.src)
            max_cost = int(np.abs(prob.cost).max())
            plan_dev = solver._plan_for(
                prob.src.astype(np.int32), prob.dst.astype(np.int32), n
            )
            import jax.numpy as jnp

            t0 = time.perf_counter()
            cold = _solve_mcmf(
                jnp.asarray(prob.cap.astype(np.int32)),
                jnp.asarray(prob.cost.astype(np.int32) * np.int32(n)),
                jnp.asarray(prob.excess.astype(np.int32)),
                jnp.asarray(np.zeros(m, np.int32)),
                jnp.asarray(np.int32(max(1, max_cost * n))),
                *plan_dev,
                alpha=solver.alpha,
                max_supersteps=200_000,
            )
            jax.block_until_ready(cold[0])
            arm["cold_costscaling_supersteps"] = int(cold[2])
            arm["cold_costscaling_wall_s"] = round(time.perf_counter() - t0, 2)
            # fresh-restart control: zero flow + tightened
            # prices at eps=1 (attempt-1 cold)
            t0 = time.perf_counter()
            fresh = _solve_mcmf(
                jnp.asarray(prob.cap.astype(np.int32)),
                jnp.asarray(prob.cost.astype(np.int32) * np.int32(n)),
                jnp.asarray(prob.excess.astype(np.int32)),
                jnp.asarray(np.zeros(m, np.int32)),
                jnp.asarray(np.int32(1)),
                *plan_dev,
                alpha=solver.alpha,
                max_supersteps=4096,
            )
            jax.block_until_ready(fresh[0])
            arm["cold_fresh_restart_supersteps"] = int(fresh[2])
            arm["cold_fresh_restart_wall_s"] = round(time.perf_counter() - t0, 2)
        out_arms[label] = arm

    # bit-parity across the three same-policy arms, every round. An
    # arm that stalled mid-run (recorded above as data) simply stops
    # contributing rounds; parity is asserted over whatever overlap
    # exists — at least two arms per compared round.
    compared = 0
    for r, per_arm in sorted(placements_by_round.items()):
        present = [a for a in _PARITY_ARMS if a in per_arm]
        if len(present) < 2:
            continue
        base = per_arm[present[0]]
        for a in present[1:]:
            assert per_arm[a] == base, (
                f"round {r}: arm {a!r} placements diverged from "
                f"{present[0]!r} ({len(per_arm[a])} vs {len(base)} bindings)"
            )
        compared += 1

    def _improvement(a, b):
        if a.get("p50_ms") and b.get("p50_ms"):
            return round(1.0 - a["p50_ms"] / b["p50_ms"], 3)
        return "arm stalled before measuring"

    dr = out_arms["device_resident"]
    fr = out_arms["full_rebuild"]
    ref = out_arms["reference"]
    r11 = out_arms["r11_policy"]
    target_ms = 10.0
    dr_p50 = dr.get("p50_ms")
    return {
        "metric": (
            f"p50 scheduling-round latency, {tasks} tasks x {machines} "
            f"machines, {churn:.0%} churn, device-resident incremental "
            f"rounds (event path), backend=jax/"
            f"{jax.devices()[0].platform}"
        ),
        "value": dr_p50,
        "unit": "ms",
        "vs_baseline": (
            round(target_ms / max(dr_p50, 1e-9), 3) if dr_p50 else 0.0
        ),
        "detail": {
            "arms": out_arms,
            "placements_bit_identical_across_arms": True,
            "parity_rounds_compared": compared,
            "p50_improvement_vs_full_rebuild": _improvement(dr, fr),
            "p50_improvement_vs_reference_path": _improvement(dr, ref),
            "p50_improvement_vs_r11_policy": _improvement(dr, r11),
            "restart_budget": restart_budget,
            "rounds": rounds,
            "warmup_rounds": warmup,
            "churn_tasks_per_round": k,
        },
    }


def _multitenant_bench(
    cells: int = 16,
    rounds: int = 24,
    warmup: int = 4,
    restart_budget: int = 64,
    verbose: bool = False,
) -> dict:
    """The multi-tenant scheduler-as-a-service benchmark (tenancy/):
    N mixed-size cells served by ONE warm process, comparing

    - ``batched``: every cell dispatches its round, then same-bucket
      lanes solve through one stacked program per (bucket, policy)
      group (solver/jax_solver.stacked_solve_fn) — the multi-tenant
      service's hot path;
    - ``sequential``: the same N cells solved one at a time, each by
      its own plain JaxSolver — the one-process-per-tenant status quo
      folded into a single loop (per-tenant warm state kept, so this
      is the strongest sequential baseline, not a strawman).

    The arms run the IDENTICAL seeded scenario (same per-cell id
    streams, same churn draws) and are interleaved round-robin so
    ambient drift hits both equally (paired, like the churn bench);
    per-cell placements are asserted bit-identical across arms every
    round — the batched stack must change WHERE lanes solve, never
    what they compute. Cell sizes cycle 3 classes so the fleet spans
    3 pow2 shape buckets; with per-lane warm scopes agreeing in
    steady state the fleet solves in ~3 stacked programs per round
    instead of N solver calls. On CPU the win is dispatch/compile-
    cache amortization; the lane-axis vectorization gain is a device
    property (UNMEASURED until a TPU ambient appears — same posture
    as the mega/device claims)."""
    import jax

    from ksched_tpu.drivers import add_job, build_cluster
    from ksched_tpu.drivers.synthetic import add_task_to_job
    from ksched_tpu.solver.jax_solver import JaxSolver
    from ksched_tpu.tenancy import LaneSolver, StackedBatcher
    from ksched_tpu.utils import seed_rng
    from ksched_tpu.utils.ids import rng as global_rng

    #: (machines, tasks) per cell class — 3 classes -> 3 pow2 buckets
    SIZES = ((12, 96), (24, 192), (48, 384))

    class _Cell:
        def __init__(self, idx: int, backend):
            machines, tasks = SIZES[idx % len(SIZES)]
            self.idx = idx
            self.tasks = tasks
            # per-cell id stream, IDENTICAL across arms: both arms'
            # cell idx consumes the same seed's continuation
            seed_rng(10_000 + idx)
            self.backend = backend
            (
                self.sched, self.rmap, self.jmap, self.tmap, self.root,
            ) = build_cluster(
                num_machines=machines, num_cores=1, pus_per_core=4,
                max_tasks_per_pu=4, backend=backend,
            )
            self.job_id = add_job(
                self.sched, self.jmap, self.tmap, num_tasks=tasks
            )
            self.sched.schedule_all_jobs()  # fill solve (not measured)
            self.rng = np.random.default_rng(500 + idx)
            self.k = max(1, tasks // 50)
            self._rng_state = global_rng().getstate()

        def swap_in(self):
            self._outer = global_rng().getstate()
            global_rng().setstate(self._rng_state)

        def park(self):
            self._rng_state = global_rng().getstate()
            global_rng().setstate(self._outer)

        def churn(self):
            bound = sorted(self.sched.task_bindings.items())
            idx = sorted(
                int(x) for x in self.rng.choice(len(bound), self.k, replace=False)
            )
            for i in reversed(idx):
                self.sched.handle_task_completion(self.tmap.find(bound[i][0]))
            for _ in range(self.k):
                add_task_to_job(self.job_id, self.jmap, self.tmap)
            self.sched.add_job(self.jmap.find(self.job_id))

        def placements(self):
            return {
                self.tmap.find(t).name: rid
                for t, rid in self.sched.task_bindings.items()
            }

    batcher = StackedBatcher()
    arms = {}
    arms["batched"] = [
        _Cell(i, LaneSolver(batcher, tenant=f"c{i}", restart_budget=restart_budget))
        for i in range(cells)
    ]
    arms["sequential"] = [
        _Cell(i, JaxSolver(slot_stable=False, restart_budget=restart_budget))
        for i in range(cells)
    ]
    fleet_ms = {"batched": [], "sequential": []}
    cell_ms = {
        "batched": [[] for _ in range(cells)],
        "sequential": [[] for _ in range(cells)],
    }
    ss_hist = {"batched": [], "sequential": []}
    programs_per_round = []
    for r in range(warmup + rounds):
        snaps = {}
        for label in ("batched", "sequential"):
            fleet = arms[label]
            t0 = time.perf_counter()
            if label == "batched":
                tokens = []
                for cell in fleet:
                    tc = time.perf_counter()
                    cell.swap_in()
                    cell.churn()
                    tokens.append(cell.sched.schedule_all_jobs_async())
                    cell.park()
                    cell_ms[label][cell.idx].append(
                        (time.perf_counter() - tc) * 1e3
                    )
                groups = batcher.flush()
                for cell, token in zip(fleet, tokens):
                    tc = time.perf_counter()
                    if token is not None:
                        cell.sched.finish_scheduling()
                    cell_ms[label][cell.idx][-1] += (
                        time.perf_counter() - tc
                    ) * 1e3
                if r >= warmup:
                    programs_per_round.append(groups)
            else:
                for cell in fleet:
                    tc = time.perf_counter()
                    cell.swap_in()
                    cell.churn()
                    cell.sched.schedule_all_jobs()
                    cell.park()
                    cell_ms[label][cell.idx].append(
                        (time.perf_counter() - tc) * 1e3
                    )
            wall_ms = (time.perf_counter() - t0) * 1e3
            snaps[label] = [c.placements() for c in fleet]
            if r >= warmup:
                fleet_ms[label].append(wall_ms)
                ss_hist[label].append(
                    sum(c.backend.last_supersteps for c in fleet)
                )
            else:
                # warm-up rounds carry the compiles; drop their
                # per-cell samples too so both stats cover the same
                # measured window
                for cell in fleet:
                    cell_ms[label][cell.idx].pop()
            if verbose:
                print(
                    f"# multitenant[{label}] round {r}: {wall_ms:.1f}ms",
                    file=sys.stderr,
                )
        # bit-parity per cell per round: batching must never change a
        # lane's answer
        for i in range(cells):
            assert snaps["batched"][i] == snaps["sequential"][i], (
                f"round {r}: cell {i} placements diverged between the "
                "batched and sequential arms"
            )

    def _arm_stats(label):
        lat = fleet_ms[label]
        per_cell = {
            f"cell_{i}": {
                "p50_ms": round(float(np.percentile(v, 50)), 3),
                "p99_ms": round(float(np.percentile(v, 99)), 3),
            }
            for i, v in enumerate(cell_ms[label])
            if v
        }
        return {
            "fleet_p50_ms": round(float(np.percentile(lat, 50)), 3),
            "fleet_p99_ms": round(float(np.percentile(lat, 99)), 3),
            "fleet_mean_ms": round(float(np.mean(lat)), 3),
            "supersteps_per_round_p50": int(np.percentile(ss_hist[label], 50)),
            "per_tenant": per_cell,
        }

    out_arms = {label: _arm_stats(label) for label in fleet_ms}
    b, s = out_arms["batched"], out_arms["sequential"]
    return {
        "metric": (
            f"p50 fleet-round latency, {cells} cells (mixed sizes, 3 pow2 "
            "buckets), batched stacked-CSR vs sequential-per-tenant, "
            f"backend=lane/{jax.devices()[0].platform}"
        ),
        "value": b["fleet_p50_ms"],
        "unit": "ms",
        "vs_baseline": (
            round(s["fleet_p50_ms"] / max(b["fleet_p50_ms"], 1e-9), 3)
        ),
        "detail": {
            "arms": out_arms,
            "placements_bit_identical_across_arms": True,
            "p50_improvement_vs_sequential": round(
                1.0 - b["fleet_p50_ms"] / s["fleet_p50_ms"], 3
            ),
            "stacked_programs_per_round_p50": int(
                np.percentile(programs_per_round, 50)
            ),
            "lanes": cells,
            "rounds": rounds,
            "warmup_rounds": warmup,
            "supersteps_p50": b["supersteps_per_round_p50"],
            "note": (
                "paired arms, same seeded scenario; CPU measures "
                "dispatch/compile amortization only — lane-axis device "
                "vectorization UNMEASURED (no TPU reachable)"
            ),
        },
    }


def _sharded_scale_bench(
    tasks: int = 100_000,
    machines: int = 10_000,
    rounds: int = 30,
    warmup: int = 4,
    churn: float = 0.01,
    burst_every: int = 8,
    burst_factor: int = 10,
    devices: int = 8,
    restart_budget: int = 64,
    verbose: bool = False,
) -> dict:
    """gtrace100k: the sharded rung's scale proof — 100k tasks × 10k
    machines on the event path, KEEP-MODE (preemption on, so post-fill
    graphs carry per-task leaf arcs and are genuinely non-collapsible:
    the general-graph path the fitting gate governs).

    Two PAIRED arms drive the identical seeded scenario through
    AutoSolver — dispatch included, so the escalation is measured, not
    simulated:

    - ``scan_csr``: AutoSolver with no sharded rung — every
      non-collapsible round solves on the single-chip slot-stable
      scan-CSR rung (the reference arm, run "where it fits": on the
      CPU host it always fits RAM);
    - ``sharded``: AutoSolver with the sharded rung attached and the
      HBM working-set budget set BETWEEN the per-shard and single-chip
      live sets at this bucket, so the gate escalates every
      non-collapsible round to the mesh; the device-resident mirror
      runs in sharded plan mode (per-shard routed record scatters).

    Both arms share the sharded-block plan layout (one entry order,
    one rebuild schedule), so placements are bit-identical BY
    CONSTRUCTION and asserted every round. The round timeline mixes
    steady churn rounds with BURST rounds (every `burst_every`-th
    round churns `burst_factor`× the base rate — the arrival-storm
    arm); percentiles are reported per kind.

    Measured and asserted: per-round supersteps, exact h2d bytes/round
    (packed records), plan sync kinds (delta-sized after warm-up), the
    per-superstep ICI reduction budget (3 psums — counted from the
    traced program, analysis/jaxpr_contracts), and a fitted
    latency = t_fixed + kappa·supersteps model over the measured
    rounds (tools/model_check.py's comparison target). The CROSS-CHIP
    latency win is UNMEASURED on the virtual CPU mesh (8 "devices" on
    one socket share memory bandwidth — same honest posture as the
    mega/device-resident claims); parity, delta-sized h2d, and the
    superstep/ICI counts are what a real mesh would pay.
    """
    import jax
    from jax.sharding import Mesh

    from ksched_tpu.analysis import jaxpr_contracts as jc
    from ksched_tpu.drivers import add_job, build_cluster
    from ksched_tpu.drivers.synthetic import add_task_to_job
    from ksched_tpu.graph.device_export import DeviceResidentState
    from ksched_tpu.obs import DeviceProfiler, set_profiler
    from ksched_tpu.obs.metrics import Registry
    from ksched_tpu.parallel.sharded_solver import (
        ShardedJaxSolver,
        csr_working_set_bytes,
        sharded_shard_bytes,
    )
    from ksched_tpu.solver.graph_collapse import AutoSolver
    from ksched_tpu.solver.jax_solver import JaxSolver
    from ksched_tpu.utils import seed_rng
    from ksched_tpu.utils.ids import rng as global_rng

    devs = jax.devices()
    if len(devs) < devices:
        raise SystemExit(
            f"gtrace100k needs {devices} devices (virtual CPU mesh: set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8); "
            f"got {len(devs)}"
        )
    mesh = Mesh(np.array(devs[:devices]), ("x",))
    k_base = max(1, int(tasks * churn))

    class _Arm:
        def __init__(self, label, sharded):
            self.label = label
            self.reg = Registry()
            self.prof = DeviceProfiler(registry=self.reg)
            set_profiler(self.prof)
            seed_rng(7)
            csr = JaxSolver(slot_stable=True, restart_budget=restart_budget)
            auto_kw = {}
            if sharded:
                self.sharded_backend = ShardedJaxSolver(
                    mesh, restart_budget=restart_budget
                )
                auto_kw = dict(
                    sharded=self.sharded_backend,
                    # the forcing budget is computed AFTER the fill (we
                    # need the padded bucket); start with 0 = never
                    hbm_budget_bytes=0,
                )
            self.auto = AutoSolver(csr, **auto_kw)
            (
                self.sched, self.rmap, self.jmap, self.tmap, self.root,
            ) = build_cluster(
                num_machines=machines, num_cores=1, pus_per_core=4,
                max_tasks_per_pu=4, backend=self.auto, preemption=True,
            )
            self.res = DeviceResidentState(self.sched.solver.state)
            if sharded:
                self.res.enable_sharded_plan(mesh, "x")
            else:
                # the reference arm consumes the SAME sharded-block
                # layout: one entry order + one rebuild schedule across
                # arms, so layout-rebuild timing (which legally
                # re-sorts cost-tied optima) can't confound the parity
                self.sched.solver.state.plan.enable_sharding(devices)
            self.sched.solver.device_resident = True
            self.sched.solver.resident = self.res
            self.job_id = add_job(
                self.sched, self.jmap, self.tmap, num_tasks=tasks
            )
            t0 = time.perf_counter()
            self.sched.schedule_all_jobs()
            self.fill_s = time.perf_counter() - t0
            if sharded:
                # the forcing budget, recorded in the artifact: halfway
                # between the per-shard and single-chip working sets of
                # the FILLED bucket — csr no longer "fits", the shard
                # slice does, so the gate escalates every general-graph
                # round (docs/sharding.md derives the default budget
                # this overrides and the scale where it trips unforced)
                st = self.sched.solver.state
                self.budget = (
                    sharded_shard_bytes(st.n_cap, st.m_cap, devices)
                    + csr_working_set_bytes(st.n_cap, st.m_cap)
                ) // 2
                self.auto.hbm_budget_bytes = self.budget
            self.rng = np.random.default_rng(123)
            self.lat = {"churn": [], "burst": []}
            self.ss = {"churn": [], "burst": []}
            self.lat_all = []
            self.ss_all = []
            self.paths = {}
            self.plan_kinds = {}
            self.h2d_mark = (0.0, 0.0)
            self.waived_rebuilds = 0
            self._global_rng = global_rng
            self._rng_state = global_rng().getstate()

        def h2d(self, kind):
            return self.reg.value("ksched_h2d_bytes_total", kind=kind)

        def drive_round(self, r):
            set_profiler(self.prof)
            self._global_rng().setstate(self._rng_state)
            if r == warmup:
                self.h2d_mark = (self.h2d("full_build"), self.h2d("delta"))
            kind = (
                "burst" if burst_every and r % burst_every == burst_every - 1
                else "churn"
            )
            k = k_base * (burst_factor if kind == "burst" else 1)
            sched, tmap = self.sched, self.tmap
            bound = sorted(sched.task_bindings.items())
            k = min(k, len(bound))
            idx = sorted(
                int(x) for x in self.rng.choice(len(bound), k, replace=False)
            )
            for i in reversed(idx):
                sched.handle_task_completion(tmap.find(bound[i][0]))
            for _ in range(k):
                add_task_to_job(self.job_id, self.jmap, tmap)
            sched.add_job(self.jmap.find(self.job_id))
            self._rng_state = self._global_rng().getstate()
            gen0 = sched.solver.state.generation
            t0 = time.perf_counter()
            sched.schedule_all_jobs()
            wall_ms = (time.perf_counter() - t0) * 1e3
            self.paths[self.auto.last_path] = (
                self.paths.get(self.auto.last_path, 0) + 1
            )
            snap = {
                tmap.find(t).name: rid
                for t, rid in sched.task_bindings.items()
            }
            if r < warmup:
                return snap
            pk = self.res.last_plan_kind
            if pk == "rebuild" and sched.solver.state.generation != gen0:
                self.waived_rebuilds += 1  # pow2 growth: rebuilds by design
                pk = "rebuild_pow2_growth"
            self.plan_kinds[pk] = self.plan_kinds.get(pk, 0) + 1
            self.lat[kind].append(wall_ms)
            self.ss[kind].append(self.auto.last_supersteps)
            self.lat_all.append(wall_ms)
            self.ss_all.append(self.auto.last_supersteps)
            if verbose:
                print(
                    f"# gtrace100k[{self.label}] round {r} ({kind}): "
                    f"{wall_ms:.0f}ms ss={self.auto.last_supersteps} "
                    f"path={self.auto.last_path} plan={pk}",
                    file=sys.stderr,
                )
            return snap

    try:
        arms = [_Arm("scan_csr", False), _Arm("sharded", True)]
        for r in range(warmup + rounds):
            snaps = [a.drive_round(r) for a in arms]
            assert snaps[0] == snaps[1], (
                f"round {r}: sharded placements diverged from the "
                f"scan-CSR reference arm "
                f"({len(snaps[1])} vs {len(snaps[0])} bindings)"
            )
    finally:
        set_profiler(None)

    sh = arms[1]
    ref = arms[0]
    # dispatch really escalated: every measured general-graph round of
    # the sharded arm took the sharded rung (fill/collapsible rounds
    # take dense); the reference arm never did
    assert sh.paths.get("sharded", 0) >= rounds, sh.paths
    assert "sharded" not in ref.paths, ref.paths
    assert sh.sharded_backend._plan is None, (
        "legacy build_sharded_plan ran on the slot-stable path"
    )
    # delta-sized rounds: zero plan layout rebuilds outside pow2 growth
    bad_rebuilds = sh.plan_kinds.get("rebuild", 0)
    assert bad_rebuilds == 0, (
        f"{bad_rebuilds} sharded plan rebuild(s) outside full_build/"
        f"pow2 growth (kinds: {sh.plan_kinds})"
    )
    sh.res.parity_check()
    sh.res.plan_parity_check()
    # ICI budget, counted from the traced program (loop-body psums)
    ici = jc.count_superstep_collectives(
        jc.trace_sharded_slot(64, 256, num_devices=devices)
    )
    assert ici.get("psum", 0) == 3, ici

    def _arm_stats(a):
        measured = max(len(a.lat_all), 1)
        full_b, delta_b = a.h2d("full_build"), a.h2d("delta")
        out = {
            "fill_s": round(a.fill_s, 1),
            "p50_ms": round(float(np.percentile(a.lat_all, 50)), 1),
            "p99_ms": round(float(np.percentile(a.lat_all, 99)), 1),
            "supersteps_p50": int(np.percentile(a.ss_all, 50)),
            "supersteps_max": int(max(a.ss_all)),
            "measured_rounds": len(a.lat_all),
            "autosolver_paths": dict(a.paths),
            "plan_sync_kinds": dict(a.plan_kinds),
            "waived_pow2_growth_rebuilds": a.waived_rebuilds,
            "h2d_delta_bytes_per_round": int(
                (delta_b - a.h2d_mark[1]) / measured
            ),
            "h2d_full_bytes_post_warmup": int(full_b - a.h2d_mark[0]),
        }
        for kind in ("churn", "burst"):
            if a.lat[kind]:
                out[f"{kind}_p50_ms"] = round(
                    float(np.percentile(a.lat[kind], 50)), 1
                )
                out[f"{kind}_supersteps_p50"] = int(
                    np.percentile(a.ss[kind], 50)
                )
        return out

    out_arms = {"scan_csr": _arm_stats(ref), "sharded": _arm_stats(sh)}
    # latency model over the sharded arm's measured rounds (each round
    # its own R=1 "chunk"): wall = t_fixed + kappa * supersteps
    model = _round_latency_model(
        sh.lat_all, 1, [[s] for s in sh.ss_all]
    )
    st = sh.sched.solver.state
    sh_p50 = out_arms["sharded"]["p50_ms"]
    target_ms = 10.0
    return {
        "metric": (
            f"p50 scheduling-round latency, {tasks} tasks x {machines} "
            f"machines, keep-mode churn+burst, sharded AutoSolver rung "
            f"({devices}-device mesh), backend=sharded/"
            f"{jax.devices()[0].platform}"
        ),
        "value": sh_p50,
        "unit": "ms",
        "vs_baseline": round(target_ms / max(sh_p50, 1e-9), 3),
        "detail": {
            "arms": out_arms,
            "placements_bit_identical_across_arms": True,
            "mesh_devices": devices,
            "graph_bucket": {"n_cap": st.n_cap, "m_cap": st.m_cap,
                             "entry_cap": st.plan.entry_cap,
                             "block_extent": st.plan.block_extent},
            "fitting_gate": {
                "budget_bytes": sh.budget,
                "csr_working_set_bytes": csr_working_set_bytes(
                    st.n_cap, st.m_cap
                ),
                "sharded_shard_bytes": sharded_shard_bytes(
                    st.n_cap, st.m_cap, devices
                ),
                "note": (
                    "budget forced between the two working sets so the "
                    "gate escalates at this bucket; at the 1 GiB "
                    "default the crossover sits near ~1M tasks "
                    "(docs/sharding.md)"
                ),
            },
            "ici_reductions_per_superstep": ici,
            "ici_vector_psums_per_round_p50": 3 * out_arms["sharded"][
                "supersteps_p50"
            ],
            "latency_model": model,
            "supersteps_p50": out_arms["sharded"]["supersteps_p50"],
            "rounds": rounds,
            "warmup_rounds": warmup,
            "churn_tasks_per_round": k_base,
            "burst_every": burst_every,
            "burst_factor": burst_factor,
            "restart_budget": restart_budget,
            "cross_chip_win": (
                "UNMEASURED: virtual 8-device CPU mesh shares one "
                "socket's memory bandwidth, so per-chip speedup is not "
                "observable here (same posture as the mega/device-"
                "resident claims); parity, delta-sized h2d, and the "
                "superstep/ICI budgets above are the measured facts"
            ),
        },
    }


#: the five BASELINE.json benchmark configs plus the Quincy
#: data-locality config (see run_config for each)
SUITE_CONFIGS = (
    "ref100", "10kx1k", "quincy10k", "quincy10k-multiblock", "coco50k",
    "coco50k-preempt", "whare-hetero", "gtrace12k", "gtrace12k-burst",
    "gtrace12k-coco",
)
#: configs runnable via --config but not part of the default suite
EXTRA_CONFIGS = (
    "gtrace12k-host", "mcmf-mega", "churn", "multitenant", "gtrace100k",
)


def run_config(args) -> None:
    """One BASELINE.json config, one JSON line.

    ref100       100 tasks x 10 machines, trivial (the reference's
                 fakeMachines smoke — cmd/k8sscheduler/scheduler.go:191-202).
    10kx1k       the headline north-star config.
    quincy10k    Quincy data-locality model at the north-star scale:
                 480 blocks x 3 replicas over 1k machines, one block
                 per task; per-task preference arcs ride the device
                 fast path as preference GROUPS (device_bulk group
                 mode + costmodels/quincy_device.py).
    coco50k      CoCo interference model, 50k tasks
                 (coco_interference_scores.proto): 4 task classes,
                 per-machine penalties, fused-Pallas multi-class solve.
    whare-hetero Whare-Map (whare_map_stats.proto): per-machine platform
                 factors modelling a heterogeneous fleet.
    gtrace12k    Google 2011 cluster-trace replay at 12.5k machines
                 (task_desc.proto:76-78 trace ids): synthesized trace
                 streams, elastic membership, incremental re-solves via
                 the host bulk path.
    """
    from ksched_tpu.costmodels.device_costs import (
        coco_device_cost_fn,
        whare_device_cost_fn,
    )

    rng = np.random.default_rng(7)
    name = args.config
    if name == "ref100":
        out = _device_bench(
            tasks=100, machines=10, pus=1, slots=16, jobs=3,
            churn=0.05, rounds=128, chunk=64, verbose=args.verbose,
        )
    elif name == "10kx1k":
        out = _device_bench(
            tasks=10_000, machines=1_000, pus=4, slots=4, jobs=10,
            churn=0.01, rounds=args.rounds, chunk=args.chunk,
            verbose=args.verbose,
        )
    elif name == "quincy10k":
        from ksched_tpu.costmodels.quincy_device import QuincyGroupTable

        MBv = 1 << 20
        n_blocks, G, machines = 480, 512, 1_000

        def group_setup(dev, setup_rng):
            # 64 MB cost units: block-transfer cost GAPS bound the
            # price-war depth of blocked-contention rounds — measured
            # 40x on captured tail instances (1795 -> 44 mean
            # supersteps, 3319 -> 68 max; docs/NOTES.md)
            table = QuincyGroupTable(
                num_groups=G, num_machines=machines, cost_unit_mb=64
            )
            for b in range(1, n_blocks + 1):
                table.blocks.register(
                    b, 512 * MBv,
                    setup_rng.choice(machines, size=3, replace=False).tolist(),
                )
            blocks = setup_rng.integers(1, n_blocks + 1, 10_000)
            groups = table.groups_for(
                np.zeros(10_000, np.int32), [[int(b)] for b in blocks]
            )
            table.sync(dev)
            return groups

        out = _device_bench(
            tasks=10_000, machines=machines, pus=4, slots=4, jobs=10,
            churn=0.01, rounds=args.rounds, chunk=args.chunk,
            num_groups=G,
            group_setup=group_setup,
            supersteps=1 << 17,
            decode_width=2048,
            label=(
                f"Quincy data-locality model ({n_blocks} blocks x 3 "
                f"replicas, {G} preference groups)"
            ),
            verbose=args.verbose,
        )
    elif name == "quincy10k-multiblock":
        out = _quincy_multiblock_bench(
            rounds=args.rounds, chunk=args.chunk, verbose=args.verbose
        )
    elif name == "coco50k":
        from ksched_tpu.costmodels import coco

        penalties = rng.integers(0, 40, (1_000, 4)).astype(np.int64)
        out = _device_bench(
            tasks=50_000, machines=1_000, pus=4, slots=16, jobs=20,
            churn=0.01, rounds=128, chunk=32,
            num_task_classes=4,
            class_cost_fn=coco_device_cost_fn(penalties),
            unsched_cost=coco.UNSCHEDULED_COST,
            ec_cost=0,
            supersteps=1 << 17,
            # 1024, was 4096: the r5 anatomy probe (tools/coco_anatomy)
            # measured the decode at 0.166 ms per 1024 width; churn is
            # 500/round and steady backlog ~0 at 78% occupancy, so
            # 1024 keeps 2x headroom and banks ~0.5 ms of the 2.2 ms
            # round
            decode_width=1024,
            label="CoCo interference cost model (4 classes)",
            verbose=args.verbose,
        )
    elif name == "coco50k-preempt":
        from ksched_tpu.costmodels import coco

        pov = parse_overrides(args.override, (
            "preempt_drift", "preempt_every", "preempt_global_every",
            "preempt_scope_tau", "preempt_incr_budget",
        ))
        penalties = rng.integers(0, 40, (1_000, 4)).astype(np.int64)
        out = _device_bench(
            tasks=50_000, machines=1_000, pus=4, slots=16, jobs=20,
            churn=0.01, rounds=128, chunk=32,
            num_task_classes=4,
            class_cost_fn=coco_device_cost_fn(penalties),
            unsched_cost=coco.UNSCHEDULED_COST,
            ec_cost=0,
            supersteps=1 << 17,
            preemption=True,
            continuation_discount=8,
            # Stability-aware preemption (VERDICT r3 #1): incremental
            # rounds pin residents and place the backlog through the
            # bounded 4096-row decode window; the FULL tiered re-solve
            # (Tcap-wide mover decode — a bounded window spirals on
            # this workload's thousands-of-migrations rounds) fires
            # every 16 rounds or on >10k census drift. Round cost now
            # tracks the delta, as the reference's incremental solver
            # does (placement/solver.go:60-90); quality drift vs
            # full-every-round is bounded by test and measured in
            # realized_cost.
            preempt_every=pov.get("preempt_every", 16),
            preempt_drift=pov.get("preempt_drift", 10_000),
            # Three-tier stability (VERDICT r4 #2): cadence/drift
            # rounds re-price only residents of machines whose census
            # drifted >= tau (plus the backlog); a truly GLOBAL
            # re-solve fires 1-in-128 rounds — outside p99 by
            # construction, and the documented bound on how long
            # scoping can defer multi-hop migration chains. tau=16
            # (CPU-swept: tau=12 -> scoped ss max 3641, tau=16 -> 1477
            # with the same fire rate) keeps the scope on the ~10% of
            # machines holding the concentrated drift; the 16384 mover
            # window is ~1.5x the measured scoped mover count so
            # nothing parks (docs/NOTES.md round-5: scope-on-any-
            # change + a binding window was a measured catastrophe).
            preempt_global_every=pov.get("preempt_global_every", 128),
            preempt_scope_tau=pov.get("preempt_scope_tau", 16),
            # bound the incremental-round solve; a non-converged
            # attempt escalates to the scoped tier (the measured incr
            # monsters — 42.7k and 62.3k supersteps — become
            # budget + scoped-cost rounds by construction)
            # 0 = off; the default follows the global tier — a two-tier
            # ablation (--override preempt_global_every=0) has no scoped
            # tier to escalate to
            preempt_incr_budget=(
                pov.get(
                    "preempt_incr_budget",
                    8192 if pov.get("preempt_global_every", 128) > 0 else 0,
                ) or None
            ),
            preempt_scoped_width=16_384,
            decode_width=4096,
            label=(
                "CoCo interference cost model (4 classes), preemption ON "
                "(three-tier: budgeted incremental rounds escalating to "
                "scoped re-solves over drifted columns every 16 or on "
                "census drift + global re-solve every 128)"
            ),
            verbose=args.verbose,
        )
        if pov:
            out["detail"]["overrides"] = dict(sorted(pov.items()))
    elif name == "whare-hetero":
        from ksched_tpu.costmodels import whare

        platform_factor = rng.integers(80, 140, 1_000).astype(np.int64)
        out = _device_bench(
            tasks=20_000, machines=1_000, pus=4, slots=8, jobs=20,
            churn=0.01, rounds=128, chunk=32,
            num_task_classes=4,
            class_cost_fn=whare_device_cost_fn(
                slots_per_machine=32, platform_factor=platform_factor
            ),
            unsched_cost=whare.UNSCHEDULED_COST,
            ec_cost=0,
            supersteps=1 << 17,
            decode_width=2048,
            label="Whare-Map cost model, heterogeneous platforms",
            verbose=args.verbose,
        )
    elif name == "gtrace12k":
        out = _gtrace_device_bench(verbose=args.verbose, overrides=args.override)
    elif name == "gtrace12k-burst":
        out = _gtrace_device_bench(
            verbose=args.verbose, burst=True, overrides=args.override
        )
    elif name == "gtrace12k-coco":
        out = _gtrace_device_bench(
            verbose=args.verbose, cost_model="coco", overrides=args.override
        )
    elif name == "gtrace12k-host":
        from ksched_tpu.drivers.trace_replay import TraceReplayDriver, synthesize_trace
        from ksched_tpu.solver.layered import LayeredTransportSolver

        machines, events = synthesize_trace(
            num_machines=12_500, num_tasks=60_000, duration_s=600.0, seed=11,
            machine_churn=0.02,
        )
        driver = TraceReplayDriver(
            machines, backend=LayeredTransportSolver(), slots_per_machine=8
        )
        stats = driver.replay(events, window_s=5.0, max_rounds=60)
        target_ms = 10.0
        out = {
            "metric": (
                f"p50 scheduling-round latency, Google-trace replay, "
                f"{driver.num_machines} machines, {stats.rounds} rounds "
                f"({stats.submitted} submits, {stats.finished} finishes, "
                f"{stats.evicted} evictions), 4 classes, host bulk path"
            ),
            "value": round(stats.p50_ms, 3),
            "unit": "ms",
            "vs_baseline": round(target_ms / max(stats.p50_ms, 1e-9), 3),
        }
    elif name == "churn":
        # the device-resident round-pipeline benchmark: full-rebuild vs
        # delta-scatter vs device-resident export arms at 1% churn on
        # the event path, bit-identical placements asserted per round
        # (docs/round_pipeline.md; BENCH_PIPELINE artifacts)
        pov = parse_overrides(
            args.override,
            ("tasks", "machines", "rounds", "churn", "restart_budget",
             "cold_control"),
        )
        out = _churn_pipeline_bench(
            tasks=int(pov.get("tasks", 10_000)),
            machines=int(pov.get("machines", 1_000)),
            rounds=int(pov.get("rounds", 24)),
            churn=float(pov.get("churn", 0.01)),
            restart_budget=int(pov.get("restart_budget", 64)),
            cold_control=bool(int(pov.get("cold_control", 1))),
            verbose=args.verbose,
        )
        if pov:
            out["detail"]["overrides"] = dict(sorted(pov.items()))
    elif name == "gtrace100k":
        # the sharded rung's scale proof: 100k x 10k keep-mode churn +
        # burst through AutoSolver's HBM fitting gate on the virtual
        # 8-device mesh, paired vs the single-chip scan-CSR arm with
        # bit-identical placements asserted per round
        # (docs/sharding.md; BENCH_GTRACE100K artifacts)
        pov = parse_overrides(
            args.override,
            ("tasks", "machines", "rounds", "warmup", "churn",
             "burst_every", "burst_factor", "devices", "restart_budget"),
        )
        out = _sharded_scale_bench(
            tasks=int(pov.get("tasks", 100_000)),
            machines=int(pov.get("machines", 10_000)),
            rounds=int(pov.get("rounds", 30)),
            warmup=int(pov.get("warmup", 4)),
            churn=float(pov.get("churn", 0.01)),
            burst_every=int(pov.get("burst_every", 8)),
            burst_factor=int(pov.get("burst_factor", 10)),
            devices=int(pov.get("devices", 8)),
            restart_budget=int(pov.get("restart_budget", 64)),
            verbose=args.verbose,
        )
        if pov:
            out["detail"]["overrides"] = dict(sorted(pov.items()))
    elif name == "multitenant":
        # scheduler-as-a-service: N mixed-size cells through one warm
        # batched solver vs sequential-per-tenant, paired arms with
        # bit-identical placements asserted per cell per round
        # (ksched_tpu/tenancy; docs/multitenancy.md)
        pov = parse_overrides(
            args.override, ("cells", "rounds", "warmup", "restart_budget")
        )
        out = _multitenant_bench(
            cells=int(pov.get("cells", 16)),
            rounds=int(pov.get("rounds", 24)),
            warmup=int(pov.get("warmup", 4)),
            restart_budget=int(pov.get("restart_budget", 64)),
            verbose=args.verbose,
        )
        if pov:
            out["detail"]["overrides"] = dict(sorted(pov.items()))
    elif name == "mcmf-mega":
        # the general-graph megakernel microbench (ops/mcmf_pallas.py):
        # mega vs the scan-based CSR/ELL backends on the 10k x 1k
        # graph-path instance. The kernel runs compiled (a compiler
        # refusal is recorded by name, not timed); `--override
        # interpret=1` asks for the Pallas interpreter, and the record
        # then says so and marks the device claim unmeasured
        # (tools/mcmf_mega_bench.py).
        from tools.mcmf_mega_bench import run_bench as _mega_bench

        pov = parse_overrides(
            args.override, ("tasks", "machines", "solves", "interpret")
        )
        out = _mega_bench(
            tasks=int(pov.get("tasks", 10_000)),
            machines=int(pov.get("machines", 1_000)),
            solves=int(pov.get("solves", 8)),
            interpret=bool(pov.get("interpret", 0)),
        )
        if pov:
            out["detail"]["overrides"] = dict(sorted(pov.items()))
    else:
        raise SystemExit(f"unknown config {name!r}; choose from {SUITE_CONFIGS}")
    out["config"] = name
    _emit_record(out)


def _quincy_multiblock_bench(
    rounds: int, chunk: int, verbose: bool = False
) -> dict:
    """Quincy BEYOND the maximally-compressive case: tasks read 2-3
    blocks each (signature = the SET of blocks), drawn from a skewed
    template pool larger than the group table, with fresh templates
    arriving between chunks — so the bench exercises signature
    diversity, overflow, and LRU eviction (QuincyGroupTable.evict_idle)
    rather than the one-block-per-task regime where 480 signatures fit
    G=512 trivially.

    Two phases: (1) TIMED device chunks (the standard floor-barred
    protocol) with on-device churn over the registered groups; between
    chunks the host registers new templates + evicts idle signatures
    and re-uploads the table (host->device only). (2) An UNTIMED
    host-driven quality segment where every task's true signature is
    known: each round's capped-table objective is compared against the
    EXACT full-diversity solve (every distinct signature its own row —
    the compression-loss oracle)."""
    import time

    import jax

    from ksched_tpu.costmodels.quincy_device import QuincyGroupTable
    from ksched_tpu.scheduler.device_bulk import DeviceBulkCluster
    from ksched_tpu.solver.layered import (
        LayeredProblem,
        LayeredTransportSolver,
    )
    from ksched_tpu.utils import next_pow2

    MBv = 1 << 20
    tasks, machines = 10_000, 1_000
    # G=1024 absorbs the whole ~500-signature working set (r3 measured
    # the G=512 cap costing 17.8%/26.6% realized-cost gap via ~86
    # overflowed signatures at sig_unit=cost_unit, 27 at sig 128 —
    # docs/NOTES.md); the compaction LADDER (256, 512) keeps typical
    # rounds on the 256-wide fused-kernel solve and routes the
    # ~500-active tail to a 512-wide solve instead of full-G width
    # (VERDICT r3 #2: both knobs measured, now turned).
    n_blocks, G = 480, 1024
    n_templates = 640
    rng = np.random.default_rng(7)

    # Split quanta: MB-granularity costs on multi-GB reads span ~12k
    # values and price-war depth scales with cost gaps in units
    # (unsolvable-in-budget at unit=1 on JAX-CPU); but cost and
    # signature quantization pull OPPOSITE ways — coarse costs create
    # exact cross-group ties that herd the synchronous solve (measured
    # p99 supersteps 3253 at 64 MB vs 6989 at uniform 128 MB), while a
    # coarse SIGNATURE key merges near-identical templates (overflow
    # 86 -> 27, realized gap 17.8% -> 3.6% at 128). cost 64 / sig 128
    # takes both.
    table = QuincyGroupTable(
        num_groups=G, num_machines=machines,
        cost_unit_mb=64, sig_unit_mb=128,
    )
    # Heavy-tailed block sizes (128 MB .. 4 GB): with uniform sizes a
    # multi-block read has NO preferred machine (no single holder
    # clears Quincy's >50% locality threshold, PREFERENCE_FRACTION),
    # and every template collapses to one no-preference signature. A
    # dominant block per read is what makes multi-block signatures
    # both diverse AND preference-carrying — the regime this config
    # exists to measure.
    sizes = (128 * MBv * np.exp(rng.exponential(1.2, n_blocks))).astype(
        np.int64
    )
    sizes = np.minimum(sizes, 4096 * MBv)
    for b in range(1, n_blocks + 1):
        table.blocks.register(
            b, int(sizes[b - 1]),
            rng.choice(machines, size=3, replace=False).tolist(),
        )

    def new_template():
        k = int(rng.integers(2, 4))  # 2-3 blocks
        return sorted(rng.choice(n_blocks, size=k, replace=False) + 1)

    templates = [new_template() for _ in range(n_templates)]
    # skewed popularity (the map-task pattern: few hot inputs)
    popularity = 1.0 / np.arange(1, n_templates + 1) ** 0.8
    popularity /= popularity.sum()

    def draw_groups(n):
        t_idx = rng.choice(n_templates, size=n, p=popularity)
        return (
            table.groups_for(
                np.zeros(n, np.int32), [templates[t] for t in t_idx]
            ),
            t_idx,
        )

    dev = DeviceBulkCluster(
        num_machines=machines, pus_per_machine=4, slots_per_pu=4,
        num_jobs=10, task_capacity=next_pow2(tasks + 4096),
        num_groups=G, supersteps=1 << 17, decode_width=2048,
        # measured active rows p50/p99/max = 91/96/99 (BENCH_SUITE r4):
        # the 128-wide rung carries virtually every round at about half
        # the 256-wide per-superstep cost; 256/512 catch diversity
        # spikes, full 1024 the pathological rest
        active_groups_cap=(128, 256, 512),
        # heavy-tailed discounts want the n/4 stage-1 schedule:
        # captured tail rounds 3580/3500 -> 51/261 supersteps (r4
        # sweep; the eps=1 schedule pays ~190-unit descents in unit
        # bounces)
        two_stage_eps0="quarter",
    )
    init_groups, _ = draw_groups(tasks)
    table.sync(dev)
    sigs_initial = len(table._sig2gid)
    dev.add_tasks(
        tasks, rng.integers(0, 10, tasks).astype(np.int32),
        groups=init_groups,
    )
    fill = dev.round()
    jax.block_until_ready(fill)

    platform = jax.devices()[0].platform
    min_wall_ms = MIN_CHUNK_WALL_MS if platform != "cpu" else 0.0
    churn_n = 100

    def maintain_table():
        """Between chunks: fresh templates arrive, idle signatures age
        out. Live counts come from the fetched state (outside any
        timed region); the refreshed table re-uploads host->device."""
        st = dev.fetch_state()
        live = np.asarray(st["live"])
        grp = np.asarray(st["grp"])
        live_per_group = np.bincount(grp[live], minlength=G)
        table.evict_idle(live_per_group, keep_fraction=0.75)
        for _ in range(32):
            templates[int(rng.integers(0, n_templates))] = new_template()
        # touch a sample so new templates register (and count overflow)
        _ = draw_groups(256)
        table.sync(dev)
        # on-device arrivals draw only REGISTERED signatures (freed
        # rows are not valid commodities until reused)
        occupied = sorted(table._sig2gid.values())
        dev.set_arrival_groups(np.unique(occupied))

    def timed_chunk(R, seed):
        t0 = time.perf_counter()
        stats = dev.run_steady_rounds(R, 0.01, churn_n, seed=seed)
        jax.block_until_ready(stats)
        np.asarray(jax.device_get(stats["live"][-1]))
        return (time.perf_counter() - t0) * 1e3, stats

    R = min(chunk, rounds)
    while True:
        jax.block_until_ready(dev.run_steady_rounds(R, 0.01, churn_n, seed=1))
        probe_ms, _ = timed_chunk(R, seed=1)
        if probe_ms >= 4 * min_wall_ms or R >= (1 << 20):
            break
        R *= 8
    if probe_ms < min_wall_ms:
        raise RuntimeError(f"chunk wall {probe_ms:.2f} ms unmeasurable")

    # round cost varies WIDELY across table-maintenance epochs (an
    # eviction sweep can leave a chunk 10x cheaper than the probe's),
    # so undercut chunks grow R and restart, as _device_bench does
    while True:
        chunks = max(3, -(-rounds // R))
        per_round_ms, chunk_walls, chunk_stats = [], [], []
        grown = False
        for rep in range(chunks):
            maintain_table()
            wall, stats = timed_chunk(R, seed=2 + rep)
            if wall < min_wall_ms:
                wall, stats = timed_chunk(R, seed=100 + rep)
            if wall < min_wall_ms:
                if R >= (1 << 20):
                    raise RuntimeError(
                        f"chunk {rep} wall {wall:.1f} ms below the bar "
                        f"at R={R} - rejecting the measurement"
                    )
                R *= 4
                warm = dev.run_steady_rounds(R, 0.01, churn_n, seed=1)
                jax.block_until_ready(warm)
                np.asarray(jax.device_get(warm["live"][-1]))
                grown = True
                break
            per_round_ms.append(wall / R)
            chunk_walls.append(round(wall, 1))
            chunk_stats.append(stats)
        if not grown:
            break

    ss_all, act_all = [], []
    for stats in chunk_stats:
        got = dev.fetch_stats(stats)
        assert got["converged"].all(), "a steady round did not converge"
        ss_all.append(np.asarray(got["supersteps"]))
        if "active_groups" in got:
            act_all.append(np.asarray(got["active_groups"]))
    from ksched_tpu.obs import soltel

    soltel.publish_round_supersteps(
        np.concatenate(ss_all), backend=f"device/{platform}"
    )

    # ---- untimed quality segment: capped table vs exact diversity ----
    solver = LayeredTransportSolver(max_supersteps=1 << 17)
    quality = _multiblock_quality_probe(
        table, templates, popularity, rng, solver, machines
    )

    ss_cat = np.concatenate(ss_all)
    p50 = float(np.percentile(per_round_ms, 50))
    target_ms = 10.0
    detail = {
        "rounds_per_chunk": R,
        "chunks_wall_ms": chunk_walls,
        "floor_bar_ms": round(min_wall_ms, 1),
        "signatures_initial": sigs_initial,
        "signatures_final": len(table._sig2gid),
        "overflow_distinct": table.overflowed,
        "evicted": table.evicted,
        "supersteps_p50": int(np.percentile(ss_cat, 50)),
        "supersteps_p99": int(np.percentile(ss_cat, 99)),
        "supersteps_max": int(ss_cat.max()),
        "latency_model": _round_latency_model(
            np.array(chunk_walls), R, ss_all
        ),
        **quality,
    }
    if act_all:
        act_cat = np.concatenate(act_all)
        detail["active_groups_p50"] = int(np.percentile(act_cat, 50))
        detail["active_groups_p99"] = int(np.percentile(act_cat, 99))
        detail["active_groups_max"] = int(act_cat.max())
    return {
        "metric": (
            f"p50 scheduling-round latency, {tasks} tasks x {machines} "
            f"machines, Quincy multi-block (2-3 blocks/task, "
            f"{n_templates} templates, G={G} + LRU eviction), 1% churn, "
            f"device-resident rounds ({R}-round chains), "
            f"backend=device/{platform}"
        ),
        "value": round(p50, 4),
        "unit": "ms",
        "vs_baseline": round(target_ms / p50, 3),
        "detail": detail,
    }


def _multiblock_quality_probe(
    table, templates, popularity, rng, solver, machines, n_rounds=8
):
    """Compression-loss oracle: for synthetic backlogs drawn from the
    template pool, solve (a) the CAPPED-table grouping (tasks of
    overflowed signatures pooled in the conservative overflow row,
    preferences lost) vs (b) the EXACT full-diversity grouping (every
    distinct signature its own row, all preferences kept) on identical
    machine capacity — then price BOTH placements at the TRUE per-task
    costs (each task's real template row). The realized-cost gap is the
    honest price of the static G cap: the capped solve's REPORTED
    objective also carries the overflow row's deliberate overcharge,
    which is accounting conservatism, not placement loss."""
    from ksched_tpu.costmodels.quincy import PREFERENCE_FRACTION
    from ksched_tpu.costmodels.quincy_device import _transfer_cost
    from ksched_tpu.solver.layered import LayeredProblem

    def true_row(t):
        total = 0
        local = {}
        for b in templates[t]:
            size = table.blocks.size(b)
            total += size
            for m in table.blocks.holders(b):
                local[m] = local.get(m, 0) + size
        unit = table.cost_unit_mb
        worst = _transfer_cost(total, 0, unit)
        row = np.full(machines, worst, np.int64)
        # same preference rule AND cost quantum as group_for, so the
        # gap measures the G cap, not a policy difference
        threshold = PREFERENCE_FRACTION * total
        for m, loc in local.items():
            if loc > threshold and 0 <= m < machines:
                row[m] = min(row[m], _transfer_cost(total, loc, unit))
        return row, worst

    def realized_cost(y, row_tasks):
        """Price a solve's placement at true per-task costs: tasks of
        each solved row take that row's machine grants in order (tasks
        within a row are interchangeable TO THE SOLVER; their true
        costs differ only in pooled overflow rows, where the in-order
        assignment is as arbitrary as the decode's)."""
        total = 0
        for r, tasks_r in enumerate(row_tasks):
            grants = y[r]
            ti = 0
            for m in np.nonzero(grants)[0]:
                for _ in range(int(grants[m])):
                    t = tasks_r[ti]
                    total += int(true_rows[t][0][m])
                    ti += 1
            for t in tasks_r[ti:]:  # unplaced: true escape cost
                total += int(true_rows[t][1] + 1)
        return total

    gaps = []
    n_templates = len(templates)
    true_rows = {t: true_row(t) for t in range(n_templates)}
    for _ in range(n_rounds):
        n = 200
        t_idx = rng.choice(n_templates, size=n, p=popularity)
        cap = rng.integers(0, 3, machines).astype(np.int32)

        # (a) capped table rows
        groups = table.groups_for(
            np.zeros(n, np.int32), [templates[t] for t in t_idx]
        )
        sup_a = np.bincount(groups, minlength=table.G).astype(np.int32)
        route_a = np.minimum(
            np.broadcast_to(table.e[:, None], (table.G, machines)),
            table.pref_w,
        ).astype(np.int64)
        act = np.nonzero(sup_a > 0)[0]
        res_a = solver.solve_layered(
            LayeredProblem(
                supply=sup_a[act],
                col_cap=cap,
                cost_cm=route_a[act].astype(np.int32),
                unsched_cost=0, ec_cost=0,
                row_unsched_cost=table.effective_u()[act],
            )
        )
        row_tasks_a = [
            [int(t) for t, g in zip(t_idx, groups) if g == gid]
            for gid in act
        ]
        realized_a = realized_cost(res_a.y, row_tasks_a)

        # (b) exact full-diversity rows (one per distinct template)
        uniq, inv = np.unique(t_idx, return_inverse=True)
        sup_b = np.bincount(inv, minlength=len(uniq)).astype(np.int32)
        route_b = np.stack([true_rows[t][0] for t in uniq])
        u_b = np.array([true_rows[t][1] + 1 for t in uniq], np.int64)
        res_b = solver.solve_layered(
            LayeredProblem(
                supply=sup_b, col_cap=cap,
                cost_cm=route_b.astype(np.int32),
                unsched_cost=0, ec_cost=0,
                row_unsched_cost=u_b,
            )
        )
        row_tasks_b = [
            [int(t) for t in t_idx[inv == r]] for r in range(len(uniq))
        ]
        realized_b = realized_cost(res_b.y, row_tasks_b)
        gaps.append((realized_a - realized_b) / max(1, realized_b))
    return {
        "realized_cost_gap_mean": round(float(np.mean(gaps)), 5),
        "realized_cost_gap_max": round(float(np.max(gaps)), 5),
    }


def _gtrace_device_bench(
    verbose: bool = False, burst: bool = False,
    cost_model: Optional[str] = None,
    overrides: Optional[list] = None,
) -> dict:
    """BASELINE config 5 on the PRODUCTION path: Google-trace replay at
    12.5k machines through DeviceBulkCluster's scanned replay program
    (per-job unsched costs, 4 classes, elastic membership — machine
    outages mid-trace). The host stages the whole windowed event stream
    up front; each timed chunk is ONE device dispatch covering K
    consecutive trace windows, closed by the scalar-fetch barrier and
    held to the same 2 s floor bar as the steady-state configs.

    burst=True (gtrace12k-burst, VERDICT r3 #5): the same scale under
    real-trace burst statistics — arrival spikes at 6x the mean rate
    (24 bursts x 30 s) and 4 CORRELATED outages of 256 machines each
    (rack failures), on top of the independent churn. Windows during a
    spike admit ~6x the steady batch and outage windows evict
    thousands at once; the steady number's headroom either survives
    this or the exception gets measured.

    cost_model="coco" (gtrace12k-coco, VERDICT r4 #1): the same trace
    scale with the CoCo interference model pricing the 4 scheduling
    classes against the running-class census — rows are census-
    dependent, so EVERY window runs the real iterative transport at
    the full [4, 12.5k] machine width instead of the per-job closed
    form. This is the machine axis of the iterative solver at the
    reference's flagship scale (Flowlessly solves whatever graph it
    is handed, scheduling/flow/placement/solver.go:60-90); the
    supersteps_max detail proves the solves are not degenerate."""
    import time

    import jax

    from ksched_tpu.drivers.trace_replay import (
        DeviceTraceReplayDriver,
        synthesize_trace,
    )

    platform = jax.devices()[0].platform
    # CPU runs (suite --cpu / CI) scale the trace down: the full 12.5k
    # machine x 8k window scan takes hours on a host backend, and the
    # CPU clock is honest at any chunk size (min_wall_ms = 0).
    if platform == "cpu":
        n_machines, window_s, n_windows, rate = 12_500, 1.0, 96, 60.0
        K0, chunks_wanted = 24, 3
        min_wall_ms = 0.0
        if cost_model:
            # iterative [4, 12.5k] solves are ~ms on TPU but the CPU
            # backend pays them serially; fewer windows keep CI honest
            n_windows, K0 = 32, 8
    else:
        n_machines, window_s, n_windows, rate = 12_500, 1.0, 12_288, 100.0
        K0, chunks_wanted = 512, 3
        min_wall_ms = MIN_CHUNK_WALL_MS
    # the census-priced variant must be CONTENDED to be meaningful: at
    # the default 8 slots/machine the trace occupies ~12% of 100k
    # slots and any solver converges in a handful of supersteps. Two
    # slots/machine + a hotter arrival rate put steady residency near
    # ~75% of 25k slots — the regime where interference pricing does
    # real work (comparable to coco50k's ~78% occupancy).
    slots_per_machine = 8
    decode_width = 4096
    task_capacity = 1 << 16 if burst else 1 << 15
    if burst:
        # r5 paired A/B/A (same-hour, identical workload totals):
        # decode 4096 -> 2048 measures 9.61/6.78/7.36 ms — the burst
        # spikes admit at most 527/window, so 2048 keeps 4x headroom
        # and halves the [width, M] mover-ranking passes
        decode_width = 2048
    else:
        # steady trace admissions peak at 129/window (8x headroom at
        # 1024); the decode-width term measured 4.1 ms/round on the
        # coco variant's same-hour ablation. The plain config's own
        # paired A/B/A (10.61 / 7.45 / 7.69) was ambient-dominated —
        # the adoption rests on the headroom argument plus the
        # coco-variant measurement, and on identical workload totals
        # in the B run
        decode_width = 1024
    if cost_model:
        slots_per_machine = 2
        rate = 160.0 if platform != "cpu" else 60.0
        # r5 ablation (BENCH_GTRACE_ABLATION_r05): at M=12.5k the
        # iterative config's cost was machinery, not supersteps —
        # decode 4096 -> 1024 saves 4.1 ms/round (admissions p50 160 /
        # max 199 per window; 1024 is 5x headroom) and Tcap 65536 ->
        # 32768 saves ~2.1 ms of Tcap-wide scans (steady live ~19.2k
        # at 160/s x 120 s runtimes). 12.48 -> 4.63 ms p50 measured,
        # identical placed/finished totals.
        decode_width = 1024
        task_capacity = 1 << 15
    # --override k=v ablation knobs (round-anatomy forensics — a
    # deviation from the named config is recorded in the metric line)
    ov = parse_overrides(overrides, (
        "n_machines", "rate", "slots_per_machine", "decode_width",
        "task_capacity", "n_windows",
    ))
    n_machines = int(ov.get("n_machines", n_machines))
    rate = float(ov.get("rate", rate))
    slots_per_machine = int(ov.get("slots_per_machine", slots_per_machine))
    decode_width = int(ov.get("decode_width", decode_width))
    task_capacity = int(ov.get("task_capacity", task_capacity))
    if "n_windows" in ov:
        n_windows = int(ov["n_windows"])
    duration_s = n_windows * window_s
    num_tasks = int(duration_s * rate)
    burst_kw = {}
    if burst:
        burst_kw = dict(
            burst_spike=6.0,
            burst_count=max(2, n_windows // 340),  # ~24 at 8192 windows
            burst_s=30.0 if n_windows > 512 else 4.0,
            correlated_outages=4,
            outage_block=max(8, n_machines // 50),  # 2% of the fleet
        )
    machines, events = synthesize_trace(
        num_machines=n_machines, num_tasks=num_tasks,
        duration_s=duration_s, mean_runtime_s=120.0, seed=11,
        machine_churn=0.02,
        **burst_kw,
    )
    policy_kw = {}
    if cost_model == "coco":
        from ksched_tpu.costmodels import coco
        from ksched_tpu.costmodels.device_costs import coco_device_cost_fn

        pen_rng = np.random.default_rng(7)
        penalties = pen_rng.integers(0, 40, (n_machines, 4)).astype(
            np.int64
        )
        policy_kw = dict(
            class_cost_fn=coco_device_cost_fn(penalties),
            unsched_cost=coco.UNSCHEDULED_COST,
            supersteps=1 << 17,
        )
    elif cost_model is not None:
        raise SystemExit(f"unknown gtrace cost_model {cost_model!r}")
    driver = DeviceTraceReplayDriver(
        machines, slots_per_machine=slots_per_machine, num_jobs_hint=64,
        task_capacity=task_capacity,
        decode_width=decode_width,
        **policy_kw,
    )
    t0 = time.perf_counter()
    sch = driver.stage(events, window_s=window_s)
    if verbose:
        print(
            f"# staged {sch['rounds']} windows ({sch['submitted']} submits, "
            f"{sch['finished']} finishes, {sch['dropped']} dropped) in "
            f"{time.perf_counter() - t0:.1f}s",
            file=sys.stderr,
        )

    def slice_schedule(i0, k):
        return {
            key: (v[i0 : i0 + k] if isinstance(v, np.ndarray) else v)
            for key, v in sch.items()
        }

    def timed_chunk(i0, k, seed):
        t0 = time.perf_counter()
        stats = driver.replay(slice_schedule(i0, k), seed=seed)
        jax.block_until_ready(stats)
        np.asarray(jax.device_get(stats["live"][-1]))
        return (time.perf_counter() - t0) * 1e3, stats

    total = sch["rounds"]
    K = min(K0, total // (chunks_wanted + 1))
    i0 = 0
    # warm chunk: compile + advance into the steady regime
    wall, _ = timed_chunk(i0, K, seed=1)
    i0 += K
    # 3x margin, not 2x: the replay configs carry ~2x ambient variance
    # on the shared host (docs/NOTES.md) — a warm chunk at 2.1x the bar
    # can be followed by timed chunks UNDER it when the ambient load
    # lifts mid-run (measured: 4.1 s warm, 1.97 s chunk 3)
    while min_wall_ms and wall < 3 * min_wall_ms and i0 + (chunks_wanted + 1) * 2 * K <= total:
        K *= 2
        wall, _ = timed_chunk(i0, K, seed=1)  # recompile at the new K
        i0 += K
    chunk_walls, chunk_stats = [], []
    timed_lo = i0
    while len(chunk_walls) < chunks_wanted and i0 + K <= total:
        wall, stats = timed_chunk(i0, K, seed=2 + len(chunk_walls))
        i0 += K
        if wall < min_wall_ms:
            # a chunk dipped under the bar mid-measurement (ambient
            # lift): grow K and restart the measured set if the staged
            # stream has room, else fail honestly
            if i0 + (chunks_wanted + 1) * 2 * K <= total:
                K *= 2
                wall, _ = timed_chunk(i0, K, seed=1)  # recompile+warm
                i0 += K
                chunk_walls, chunk_stats = [], []
                timed_lo = i0
                continue
            raise RuntimeError(
                f"gtrace chunk wall {wall:.1f} ms under the "
                f"{min_wall_ms:.0f} ms bar at K={K} with no windows left "
                "to grow into"
            )
        chunk_walls.append(round(wall, 1))
        chunk_stats.append(stats)
    # burst-coverage evidence: admission-batch stats of the TIMED
    # window range (a burst claim is only as good as the spikes the
    # clock actually saw)
    adm_timed = sch["adm_n"][timed_lo:i0]
    if len(chunk_walls) < 2:
        raise RuntimeError("not enough staged windows for 2 measured chunks")

    per_round_ms = [w / K for w in chunk_walls]
    ss_all, evicted, placed = [], 0, 0
    for stats in chunk_stats:
        got = driver.cluster.fetch_stats(stats)
        assert got["converged"].all(), "a replay round did not converge"
        ss_all.append(np.asarray(got["supersteps"]))
        evicted += int(got["evicted"].sum())
        placed += int(got["placed"].sum())
    from ksched_tpu.obs import soltel

    soltel.publish_round_supersteps(
        np.concatenate(ss_all), backend=f"device/{platform}"
    )
    p50 = float(np.percentile(per_round_ms, 50))
    target_ms = 10.0
    detail = {
        "rounds_per_chunk": K,
        "chunks_wall_ms": chunk_walls,
        "floor_bar_ms": round(min_wall_ms, 1),
        "windows_total": total,
        "submitted": sch["submitted"],
        "finished": sch["finished"],
        "evicted_measured": evicted,
        "placed_measured": placed,
        "adm_per_window_timed_p50": int(np.percentile(adm_timed, 50)),
        "adm_per_window_timed_max": int(adm_timed.max()),
        "supersteps_max": int(np.concatenate(ss_all).max()),
        "latency_model": _round_latency_model(
            np.array(chunk_walls), K, ss_all
        ),
    }
    burst_tag = (
        "BURST arrivals (6x spikes) + correlated rack outages, "
        if burst else ""
    )
    ss_cat = np.concatenate(ss_all)
    detail["supersteps_p50"] = int(np.percentile(ss_cat, 50))
    if ov:
        detail["overrides"] = {k: ov[k] for k in sorted(ov)}
    policy_tag = (
        "CoCo census-priced classes (iterative transport every window)"
        if cost_model == "coco" else "per-job unsched"
    )
    return {
        "metric": (
            f"p50 scheduling-round latency, Google-trace replay, "
            f"{n_machines} machines, {total} windows staged, 4 classes, "
            f"{policy_tag}, elastic membership, {burst_tag}"
            f"device replay scan ({K}-round chunks), backend=device/{platform}"
        ),
        "value": round(p50, 4),
        "unit": "ms",
        "vs_baseline": round(target_ms / p50, 3),
        "detail": detail,
    }


def _suite_stamp(first_record: dict) -> dict:
    """Provenance header for the suite artifact: commit, device, env.
    The reference's measurement point is a RECORDED per-round print
    (cmd/k8sscheduler/scheduler.go:146-150); the rebuild's equivalent
    must be a committed file, not prose (VERDICT r3 missing #1).

    The device comes from the first child's record: the suite parent
    never initialises a JAX backend (a chip belongs to one process at
    a time, and the children need it)."""
    import subprocess
    from importlib.metadata import version

    try:
        commit = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)), timeout=10,
        ).stdout.strip()
    except (OSError, subprocess.TimeoutExpired):
        commit = ""
    device = first_record["device"]
    return {
        "suite_stamp": True,
        "commit": commit or "unknown",
        "platform": device["platform"],
        "device": device,
        "jax": version("jax"),
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "configs": list(SUITE_CONFIGS),
    }


def run_suite(args) -> int:
    """All suite configs, each in its OWN subprocess, one at a time;
    this parent holds no JAX backend, so each child gets the chip.
    Children share the persistent compilation cache
    (ksched_tpu.utils.enable_compile_cache).

    Every run writes its own machine-readable artifact (--suite-out,
    default BENCH_SUITE.jsonl next to this file): a provenance stamp
    line, then one JSON line per config — the committed equivalent of
    the reference's recorded round timer.

    A child that fails before ANY record exists (no accelerator, a
    broken install) ends the suite non-zero with no line printed; a
    later config's failure is recorded by name and the suite exits 1."""
    import subprocess

    out_path = args.suite_out
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_SUITE.jsonl"
        )
    lines = []
    failed = 0

    def emit(line: str) -> None:
        print(line)
        lines.append(line)
        # rewrite on every config so a crashed/interrupted suite still
        # leaves a valid partial artifact
        with open(out_path, "w") as f:
            f.write("\n".join(lines) + "\n")

    for name in SUITE_CONFIGS:
        cmd = [sys.executable, os.path.abspath(__file__), "--config", name,
               "--rounds", str(args.rounds), "--chunk", str(args.chunk)]
        if args.cpu:
            cmd.append("--cpu")
        if args.verbose:
            cmd.append("--verbose")
        r = subprocess.run(cmd, capture_output=True, text=True)
        if args.verbose and r.stderr:
            sys.stderr.write(r.stderr)
        line = (r.stdout.strip().splitlines() or ["<no output>"])[-1]
        if r.returncode != 0:
            if not lines:
                sys.stderr.write(r.stderr)
                raise SystemExit(
                    f"suite: first config {name!r} failed (exit "
                    f"{r.returncode}) before any record; nothing measured"
                )
            failed += 1
            emit(json.dumps({"metric": f"config {name} FAILED", "value": None,
                             "unit": "ms", "vs_baseline": 0.0,
                             "config": name,
                             "error": (r.stderr or line)[-400:]}))
            continue
        if not lines:
            emit(json.dumps(_suite_stamp(json.loads(line))))
        emit(line)
    print(f"# suite artifact: {out_path}", file=sys.stderr)
    return 1 if failed else 0


def build(args):
    from ksched_tpu.scheduler.bulk import BulkCluster

    from ksched_tpu.solver.select import make_backend

    name = "auto" if args.backend == "autograph" else args.backend
    backend = make_backend(name, warm_start=not args.cold, fallback=False)
    cluster = BulkCluster(
        num_machines=args.machines,
        pus_per_machine=args.pus,
        slots_per_pu=args.slots,
        num_jobs=args.jobs,
        backend=backend,
        task_capacity=args.tasks + 4096,
    )
    return cluster, backend


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tasks", type=int, default=10_000)
    ap.add_argument("--machines", type=int, default=1_000)
    ap.add_argument("--pus", type=int, default=4, help="PUs per machine")
    ap.add_argument("--slots", type=int, default=4, help="slots per PU")
    ap.add_argument("--jobs", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=512, help="total measured rounds")
    ap.add_argument("--churn", type=float, default=0.01)
    ap.add_argument("--cold", action="store_true", help="no warm start between rounds")
    ap.add_argument("--small", action="store_true", help="quick smoke (100 tasks x 10 machines)")
    ap.add_argument("--cpu", action="store_true", help="run host-only on JAX-CPU (skip the accelerator); combine with --backend native/ref for the host solver paths")
    ap.add_argument(
        "--backend",
        choices=["auto", "device", "layered", "jax", "ell", "mega",
                 "native", "ref", "autograph"],
        default="auto",
        help=(
            "scheduling path: device = device-resident cluster (the TPU "
            "production path), layered/jax/ell/mega/native/ref = host "
            "cluster with that MCMF backend (mega = the VMEM-resident "
            "Pallas megakernel, compiled — an error carrying the "
            "compiler's message where Mosaic refuses it), autograph "
            "= host cluster with the per-solve dense -> mega -> CSR "
            "dispatch (make_backend('auto')); auto = device"
        ),
    )
    ap.add_argument(
        "--chunk", type=int, default=64,
        help="device path: rounds per on-device scan chunk",
    )
    ap.add_argument(
        "--suite", action="store_true",
        help="run all five BASELINE.json configs (prints one JSON line "
        "per config instead of the single headline line); --rounds/"
        "--chunk apply only to the 10kx1k config — the others use "
        "fixed per-config budgets",
    )
    ap.add_argument(
        "--config", choices=SUITE_CONFIGS + EXTRA_CONFIGS, default=None,
        help="run a single named BASELINE.json config",
    )
    ap.add_argument(
        "--suite-out", default=None, metavar="PATH",
        help="suite artifact path (default: BENCH_SUITE.jsonl next to "
        "bench.py); written incrementally, one JSON line per config "
        "after a provenance stamp line",
    )
    ap.add_argument(
        "--override", action="append", default=[], metavar="K=V",
        help="config-knob override for round-anatomy ablations "
        "(gtrace configs: n_machines, rate, slots_per_machine, "
        "decode_width, task_capacity, n_windows); recorded in the "
        "output record",
    )
    ap.add_argument(
        "--obs-out", default=None, metavar="PATH",
        help="write the obs metrics-registry snapshot JSON at exit "
        "(ksched_tpu/obs; docs/observability.md)",
    )
    ap.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="record obs spans during the measured rounds and write a "
        "Chrome/Perfetto trace-event JSON at exit",
    )
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()

    if args.small:
        args.tasks, args.machines, args.rounds = 100, 10, 128
    if args.cpu:
        # an explicit request for the host; must precede `import jax`
        os.environ["JAX_PLATFORMS"] = "cpu"

    if args.suite:
        if args.trace_out or args.obs_out:
            # each suite config runs in its own subprocess; a tracer or
            # registry in this parent would capture nothing
            ap.error(
                "--trace-out/--obs-out apply to a single run, not "
                "--suite (pass them to one config instead)"
            )
        return run_suite(args)

    from ksched_tpu.utils import enable_compile_cache, require_accelerator

    enable_compile_cache()
    if not args.cpu:
        # no fallback: without a chip nothing is measured or printed
        require_accelerator("bench.py")

    span_tracer = None
    if args.trace_out:
        from ksched_tpu.obs import SpanTracer

        span_tracer = SpanTracer().install()
    try:
        if args.config:
            return run_config(args)
        if args.backend in ("auto", "device"):
            args.backend = "device"
            return run_device_bench(args)
        return _run_bulk_bench(args)
    finally:
        if span_tracer is not None:
            span_tracer.uninstall()
            span_tracer.dump(args.trace_out)
            print(f"# obs: span trace -> {args.trace_out}", file=sys.stderr)
            if span_tracer.total == 0:
                print(
                    "# obs: WARNING: no spans were recorded — spans cover "
                    "the host bulk/layered round paths; the device-resident "
                    "path runs fused inside jit and records none",
                    file=sys.stderr,
                )
        if args.obs_out:
            from ksched_tpu.obs import dump_registry, get_registry

            reg = get_registry()
            dump_registry(reg, args.obs_out)
            print(f"# obs: registry snapshot -> {args.obs_out}", file=sys.stderr)
            fams = {f.name for f in reg.collect()}
            if not fams:
                print(
                    "# obs: WARNING: the registry snapshot is empty — "
                    "enable obs (drop KSCHED_OBS=0/--no-obs) to record",
                    file=sys.stderr,
                )
            elif "ksched_solve_supersteps" not in fams:
                # device-fused paths and the compiled host backends all
                # publish solver-interior telemetry now; only backends
                # that genuinely expose none land here
                print(
                    "# obs: WARNING: no solver-interior telemetry was "
                    "recorded — the native/cpu_ref backends expose no "
                    "superstep counters (docs/observability.md, Solver "
                    "interior)",
                    file=sys.stderr,
                )


def _publish_bench_obs(lat_ms, rounds_meta) -> None:
    """Mirror the measured rounds onto the obs metrics registry AFTER
    the clock stops, so --obs-out snapshots carry the same round/phase
    series the service publishes live while the measured loop itself
    performs zero registry operations — the overhead protocol in
    BENCH_OBS_OVERHEAD_r09.json depends on that. Publication goes
    through RoundTracer so the metric names, label sets, and the
    timing-key → phase mapping stay single-sourced in runtime/trace.py."""
    from ksched_tpu.runtime.trace import RoundTracer

    tracer = RoundTracer(capacity=1)  # publication only; records unused
    for total_ms, (timing, placed, work) in zip(lat_ms, rounds_meta):
        tracer.record_timed_round(
            timing, total_ms=total_ms, num_scheduled=placed, solver_work=work
        )


def _run_bulk_bench(args):
    import jax

    rng = np.random.default_rng(0)
    cluster, backend = build(args)
    devices = jax.devices()

    # Fill: admit all tasks, run rounds until placements settle.
    job_ids = rng.integers(0, args.jobs, args.tasks).astype(np.int32)
    cluster.add_tasks(args.tasks, job_ids)
    t0 = time.perf_counter()
    r = cluster.round()
    fill_s = time.perf_counter() - t0
    if args.verbose:
        print(
            f"# fill: placed {len(r.placed_tasks)}/{args.tasks} in {fill_s:.2f}s "
            f"(cold solve, incl. compile), unsched={r.num_unscheduled}, "
            f"work={_solver_work(backend)}",
            file=sys.stderr,
        )

    # Steady state: churn + measure.
    churn_n = max(1, int(args.tasks * args.churn))
    lat_ms = []
    rounds_meta = []
    for i in range(args.rounds):
        placed_rows = np.nonzero(cluster.task_pu >= 0)[0]
        done = rng.choice(placed_rows, size=min(churn_n, len(placed_rows)), replace=False)
        t0 = time.perf_counter()
        cluster.complete_tasks(cluster.task0 + done.astype(np.int32))
        cluster.add_tasks(churn_n, rng.integers(0, args.jobs, churn_n).astype(np.int32))
        r = cluster.round()
        lat_ms.append((time.perf_counter() - t0) * 1e3)
        rounds_meta.append((r.timing, len(r.placed_tasks), _solver_work(backend)))
        if args.verbose:
            t = r.timing
            print(
                f"# round {i}: {lat_ms[-1]:.2f}ms placed={len(r.placed_tasks)} "
                f"(solve={t['solve_s']*1e3:.2f} decode={t['decode_s']*1e3:.2f} "
                f"stats={t['stats_s']*1e3:.2f} apply={t['apply_s']*1e3:.2f}) "
                f"work={_solver_work(backend)}",
                file=sys.stderr,
            )

    if args.obs_out:
        _publish_bench_obs(lat_ms, rounds_meta)
    p50 = float(np.percentile(lat_ms, 50))
    target_ms = 10.0
    _emit_record(
        {
            "metric": (
                f"p50 scheduling-round latency, {args.tasks} tasks x "
                f"{args.machines} machines, trivial cost model, "
                f"{args.churn:.0%} churn, backend={args.backend}/{devices[0].platform}"
            ),
            "value": round(p50, 3),
            "unit": "ms",
            "vs_baseline": round(target_ms / p50, 3),
        },
    )


if __name__ == "__main__":
    sys.exit(main())
