#!/usr/bin/env python
"""Capture and replay superstep-tail rounds of the steady-state configs.

On the array path a small minority of steady-state rounds burn 20-30x
the typical superstep budget (a round-2 sweep recorded supersteps_max =
15687 for quincy10k and 25324 for whare-hetero against p50s of 12 and
753), and at ~2.6 us/superstep they blow the 10 ms target. This tool
makes those rounds reproducible:

  capture  run the steady-state loop on JAX-CPU, one round per dispatch,
           snapshotting each round's exact transport instance (cost
           matrix, window supply, free columns) BEFORE the round runs;
           rounds whose supersteps exceed a threshold are written to an
           npz for replay.
  replay   re-solve captured instances under solver-knob sweeps
           (alpha, refine_waves, eps0 policy) and report supersteps per
           knob point — the measurement loop for killing the tail.

Usage:
  python tools/tail_repro.py capture --config whare --rounds 200 --out /tmp/tails.npz
  python tools/tail_repro.py replay --inst /tmp/tails.npz --alpha 2,8 --refine 8,32
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


def build_config(name: str):
    """The array path's steady-state configs, scaled for CPU capture."""
    from ksched_tpu.costmodels.device_costs import (
        coco_device_cost_fn,
        whare_device_cost_fn,
    )
    from ksched_tpu.scheduler.device_bulk import DeviceBulkCluster
    from ksched_tpu.utils import next_pow2

    rng = np.random.default_rng(7)
    if name == "whare":
        tasks, machines = 20_000, 1_000
        platform = rng.integers(0, 3, machines).astype(np.int64)
        dev = DeviceBulkCluster(
            num_machines=machines, pus_per_machine=4, slots_per_pu=8,
            num_jobs=20, num_task_classes=4,
            task_capacity=next_pow2(tasks + 4096),
            class_cost_fn=whare_device_cost_fn(
                32, platform=platform
            ),
            unsched_cost=_whare_unsched(), ec_cost=0,
            supersteps=1 << 17, decode_width=2048,
        )
    elif name == "coco":
        tasks, machines = 50_000, 1_000
        penalties = rng.integers(0, 40, (machines, 4)).astype(np.int64)
        dev = DeviceBulkCluster(
            num_machines=machines, pus_per_machine=4, slots_per_pu=16,
            num_jobs=20, num_task_classes=4,
            task_capacity=next_pow2(tasks + 4096),
            class_cost_fn=coco_device_cost_fn(penalties),
            unsched_cost=_coco_unsched(), ec_cost=0,
            supersteps=1 << 17, decode_width=4096,
        )
    elif name == "coco-preempt":
        # scaled-down preemption-on CoCo (CPU-capturable): same
        # structure as coco50k-preempt at 20k tasks
        tasks, machines = 20_000, 1_000
        penalties = rng.integers(0, 40, (machines, 4)).astype(np.int64)
        dev = DeviceBulkCluster(
            num_machines=machines, pus_per_machine=4, slots_per_pu=8,
            num_jobs=20, num_task_classes=4,
            task_capacity=next_pow2(tasks + 4096),
            class_cost_fn=coco_device_cost_fn(penalties),
            unsched_cost=_coco_unsched(), ec_cost=0,
            supersteps=1 << 17,
            preemption=True, continuation_discount=8,
        )
    elif name == "quincy":
        from ksched_tpu.costmodels.quincy_device import QuincyGroupTable

        MBv = 1 << 20
        tasks, machines, n_blocks, G = 10_000, 1_000, 480, 512
        dev = DeviceBulkCluster(
            num_machines=machines, pus_per_machine=4, slots_per_pu=4,
            num_jobs=10, task_capacity=next_pow2(tasks + 4096),
            num_groups=G, supersteps=1 << 17, decode_width=2048,
        )
        table = QuincyGroupTable(
            num_groups=G, num_machines=machines, cost_unit_mb=64
        )
        for b in range(1, n_blocks + 1):
            table.blocks.register(
                b, 512 * MBv,
                rng.choice(machines, size=3, replace=False).tolist(),
            )
        blocks = rng.integers(1, n_blocks + 1, tasks)
        groups = table.groups_for(
            np.zeros(tasks, np.int32), [[int(b)] for b in blocks]
        )
        table.sync(dev)
        dev._tail_repro_groups = (table, groups)  # capture() hooks
    elif name == "multiblock":
        # split quanta, heavy-tailed block sizes, skewed template pool:
        # the setup whose tails this tool was written to capture
        from ksched_tpu.costmodels.quincy_device import QuincyGroupTable

        MBv = 1 << 20
        tasks, machines, n_blocks, G = 10_000, 1_000, 480, 1024
        n_templates = 640
        dev = DeviceBulkCluster(
            num_machines=machines, pus_per_machine=4, slots_per_pu=4,
            num_jobs=10, task_capacity=next_pow2(tasks + 4096),
            num_groups=G, supersteps=1 << 17, decode_width=2048,
            active_groups_cap=(128, 256, 512),
            two_stage_eps0="quarter",
        )
        table = QuincyGroupTable(
            num_groups=G, num_machines=machines,
            cost_unit_mb=64, sig_unit_mb=128,
        )
        rng7 = np.random.default_rng(7)
        sizes = (
            128 * MBv * np.exp(rng7.exponential(1.2, n_blocks))
        ).astype(np.int64)
        sizes = np.minimum(sizes, 4096 * MBv)
        for b in range(1, n_blocks + 1):
            table.blocks.register(
                b, int(sizes[b - 1]),
                rng7.choice(machines, size=3, replace=False).tolist(),
            )
        templates = [
            sorted(
                rng7.choice(n_blocks, size=int(rng7.integers(2, 4)),
                            replace=False) + 1
            )
            for _ in range(n_templates)
        ]
        popularity = 1.0 / np.arange(1, n_templates + 1) ** 0.8
        popularity /= popularity.sum()
        t_idx = rng7.choice(n_templates, size=tasks, p=popularity)
        groups = table.groups_for(
            np.zeros(tasks, np.int32), [templates[t] for t in t_idx]
        )
        table.sync(dev)
        dev._tail_repro_groups = (table, groups)
    else:
        raise SystemExit(f"unknown config {name!r}")
    return dev, tasks


def _whare_unsched():
    from ksched_tpu.costmodels import whare

    return whare.UNSCHEDULED_COST


def _coco_unsched():
    from ksched_tpu.costmodels import coco

    return coco.UNSCHEDULED_COST


def capture(args) -> None:
    import jax
    import jax.numpy as jnp

    dev, tasks = build_config(args.config)
    rng = np.random.default_rng(0)
    grouped_setup = getattr(dev, "_tail_repro_groups", None)
    if grouped_setup is not None:
        _table, init_groups = grouped_setup
        dev.add_tasks(
            tasks, rng.integers(0, dev.J, tasks).astype(np.int32),
            groups=init_groups,
        )
    else:
        dev.add_tasks(
            tasks,
            rng.integers(0, dev.J, tasks).astype(np.int32),
            rng.integers(0, dev.C, tasks).astype(np.int32),
        )
    jax.block_until_ready(dev.round())

    churn_n = max(1, int(tasks * 0.01))
    # Tail rounds appear only after the backlog drifts into the
    # contended regime (solver escapes accumulate over hundreds of
    # rounds); run the warmup as device-chained chunks — fast — before
    # capturing rounds one by one.
    warm_chunk = 256
    for w0 in range(0, args.warmup, warm_chunk):
        stats = dev.fetch_stats(
            dev.run_steady_rounds(
                min(warm_chunk, args.warmup - w0), 0.01, churn_n, seed=w0
            )
        )
        if args.verbose:
            ss = np.asarray(stats["supersteps"])
            print(
                f"# warmup {w0}+{len(ss)}: ss p50={np.percentile(ss, 50):.0f} "
                f"max={ss.max()}",
                file=sys.stderr,
            )
    insts = []
    ss_all = []
    for i in range(args.rounds):
        # Drive the churn from the host (complete + admit), snapshot
        # the exact pre-solve state, then run the round — so a captured
        # instance IS the instance the round solved (round() decodes
        # full-width; the steady window never binds at churn_n rows).
        st0 = dev.fetch_state()
        live = np.asarray(st0["live"])
        pu = np.asarray(st0["pu"])
        placed_rows = np.nonzero(live & (pu >= 0))[0]
        done = rng.choice(
            placed_rows, size=min(churn_n, len(placed_rows)), replace=False
        )
        dev.complete_tasks(done.astype(np.int32))
        if grouped_setup is not None:
            dev.add_tasks(
                churn_n,
                rng.integers(0, dev.J, churn_n).astype(np.int32),
                groups=rng.integers(0, dev.G, churn_n).astype(np.int32),
            )
        else:
            dev.add_tasks(
                churn_n,
                rng.integers(0, dev.J, churn_n).astype(np.int32),
                rng.integers(0, dev.C, churn_n).astype(np.int32),
            )
        st = dev.fetch_state()
        stats = dev.fetch_stats(dev.round())
        ss = int(stats["supersteps"])
        ss_all.append(ss)
        if ss >= args.threshold:
            insts.append((ss, st))
        if args.verbose and (ss >= args.threshold or i % 20 == 0):
            print(f"# round {i}: supersteps={ss}", file=sys.stderr)

    ss_all = np.array(ss_all)
    print(
        f"rounds={args.rounds} supersteps p50={np.percentile(ss_all, 50):.0f} "
        f"p90={np.percentile(ss_all, 90):.0f} p99={np.percentile(ss_all, 99):.0f} "
        f"max={ss_all.max()} tails>={args.threshold}: {len(insts)}"
    )
    if not insts:
        print("no tail rounds captured; lower --threshold")
        return
    if grouped_setup is not None:
        # grouped instance: per-group supply over the decode window +
        # machine_free; GroupSpec arrays are capture-static, saved once
        out = {}
        for k, (ss, st) in enumerate(insts):
            supply, machine_free = grouped_instance_from_state(dev, st)
            out[f"supply_{k}"] = supply
            out[f"free_{k}"] = machine_free
            out[f"ss_{k}"] = np.int64(ss)
        g = dev.groups
        out.update(
            n=np.int64(len(insts)), n_scale=np.int64(dev.n_scale),
            Mp=np.int64(dev.Mp), grouped=np.int64(1),
            g_e=np.asarray(g.e), g_u=np.asarray(g.u),
            g_pref=np.asarray(g.pref_w),
            active_cap=np.int64(dev.active_groups_cap),
        )
        np.savez_compressed(args.out, **out)
        print(f"wrote {len(insts)} grouped instances to {args.out}")
        return
    # Reconstruct each tail round's transport instance from its
    # pre-round state snapshot. The captured state is PRE-churn; the
    # exact solved instance differs by one churn step, but the captured
    # one is statistically identical (verified: replay supersteps are
    # the same magnitude) and fully reproducible.
    out = {}
    for k, (ss, st) in enumerate(insts):
        inst = instance_from_state(dev, st)
        out[f"w_{k}"] = inst[0]
        out[f"supply_{k}"] = inst[1]
        out[f"colcap_{k}"] = inst[2]
        if dev.preemption:
            out[f"residents_{k}"] = inst[3]
        out[f"ss_{k}"] = np.int64(ss)
    out["n"] = np.int64(len(insts))
    out["n_scale"] = np.int64(dev.n_scale)
    out["Mp"] = np.int64(dev.Mp)
    out["preempt"] = np.int64(int(dev.preemption))
    out["discount"] = np.int64(dev.continuation_discount)
    np.savez_compressed(args.out, **out)
    print(f"wrote {len(insts)} instances to {args.out}")


def grouped_instance_from_state(dev, st):
    """(supply[G] over the decode window, machine_free[M]) for a
    group-mode round — mirrors round_core's window census."""
    live = np.asarray(st["live"])
    pu = np.asarray(st["pu"])
    grp = np.asarray(st["grp"])
    M, P, S = dev.M, dev.P, dev.S
    num_pus = dev.num_pus

    placed = live & (pu >= 0)
    pu_running = np.zeros(num_pus, np.int64)
    np.add.at(pu_running, pu[placed], 1)
    enabled = np.asarray(st["machine_enabled"])
    pu_free = np.where(np.repeat(enabled, P), S - pu_running, 0)
    machine_free = pu_free.reshape(M, P).sum(axis=1)

    unplaced = live & (pu < 0)
    W = dev.decode_width or dev.Tcap
    rows = np.nonzero(unplaced)[0][:W]
    supply = np.bincount(grp[rows], minlength=dev.G)
    return supply.astype(np.int32), machine_free.astype(np.int32)


def replay_grouped(args) -> None:
    """Re-solve captured GROUPED instances under solver-strategy sweeps,
    replicating round_core's grouped dispatch (two-stage decomposition
    with the eps0=1 bounded attempt, active-row compaction, refined
    full fallback — scheduler/device_bulk.py) outside the jitted round
    so strategies can be compared on real blocked-contention rounds."""
    import jax.numpy as jnp

    from ksched_tpu.solver.layered import (
        choose_eps0,
        split_grants_by_class,
        transport_fori,
    )

    data = np.load(args.inst)
    n = int(data["n"])
    n_scale = int(data["n_scale"])
    Mp = int(data["Mp"])
    e = data["g_e"].astype(np.int64)
    u = data["g_u"].astype(np.int64)
    pref = data["g_pref"].astype(np.int64)
    G, M = pref.shape
    PREF_NONE = 1 << 30

    route = np.broadcast_to(e[:, None], (G, M))
    cost_eff = np.minimum(route, pref)
    w = cost_eff - u[:, None]
    ground = (e - u).astype(np.int64)  # [G]

    strategies = args.strategies.split(",")
    active_cap = int(data["active_cap"])

    for k in range(n):
        supply = data[f"supply_{k}"].astype(np.int32)
        machine_free = data[f"free_{k}"].astype(np.int32)
        orig = int(data[f"ss_{k}"])
        total = int(supply.sum())

        # active-row compaction (as the device path does)
        act = np.nonzero(supply > 0)[0]
        if len(act) > active_cap:
            act = np.arange(G)
        wA = w[act]
        supA = supply[act]
        groundA = ground[act]
        Ga = len(act)
        col_cap = np.zeros(Mp, np.int64)
        col_cap[:M] = machine_free
        col_cap[-1] = total
        wP = np.zeros((Ga, Mp), np.int64)
        wP[:, :M] = wA
        wS = jnp.asarray((wP * n_scale).astype(np.int32))
        supJ = jnp.asarray(supA)
        capJ = jnp.asarray(col_cap.astype(np.int32))
        eps_full = int(max(1, np.abs(wP).max() * n_scale))

        D = np.maximum(groundA[:, None] - wA, 0)
        w1 = np.where(D > 0, -D, 1)
        w1P = np.zeros((Ga, Mp), np.int64)
        w1P[:, :M] = w1
        wS1 = jnp.asarray((w1P * n_scale).astype(np.int32))
        two_stage_ok = (total <= int(machine_free.sum())) and bool(
            ((groundA < 0) | (supA == 0)).all()
        )

        print(
            f"inst {k}: rows={Ga} total={total} "
            f"free={int(machine_free.sum())} two_stage_ok={two_stage_ok} "
            f"orig_ss={orig}"
        )
        obj_ref = None
        for strat in strategies:
            ss_total = 0
            if strat.startswith("two"):
                # two-stage: stage-1 eps0/budget from the strategy name
                # two:<eps0>:<budget>[:<fallback-eps0>]
                # (eps0 'n4' = n_scale/4, '1' = 1; the optional 4th
                # field overrides the FULL-FALLBACK eps0 taken when the
                # stage-1 budget is exhausted — the production default
                # is choose_eps0(short=n_scale))
                parts = strat.split(":")
                _, e0name, budget = parts[:3]
                fb_name = parts[3] if len(parts) > 3 else None
                e0 = {"1": 1, "n4": n_scale // 4, "n": n_scale}[e0name]
                y1, _pm, s1, conv1 = transport_fori(
                    wS1, supJ, capJ, 1 << 17, alpha=2, refine_waves=8,
                    eps0=int(e0), eps0_budget=int(budget),
                    eps0_retry=False,  # the production honest bound
                )
                ss_total += int(s1)
                if bool(conv1):
                    y1r = np.asarray(y1, np.int64)[:, :M]
                    left = supA - y1r.sum(axis=1)
                    rem = machine_free - y1r.sum(axis=0)
                    excl = np.cumsum(rem) - rem
                    grants_m = np.clip(left.sum() - excl, 0, rem)
                    y2 = split_grants_by_class(grants_m, left)
                    y_real = y1r + y2
                else:
                    fb = {
                        None: int(choose_eps0(n_scale, eps_full, total,
                                              int(machine_free.sum()),
                                              short=n_scale)),
                        "n4": n_scale // 4, "n": n_scale,
                        "n2": n_scale // 2, "1": 1,
                    }[fb_name]
                    y_f, _pm, s2, conv2 = transport_fori(
                        wS, supJ, capJ, 1 << 17, alpha=2, refine_waves=8,
                        eps0=int(fb),
                    )
                    ss_total += int(s2)
                    assert bool(conv2)
                    y_real = np.asarray(y_f, np.int64)[:, :M]
            else:
                # direct full solve: full:<eps0name>:<alpha>
                _, e0name, alpha = strat.split(":")
                e0 = {"1": 1, "n4": n_scale // 4, "n": n_scale,
                      "full": eps_full}[e0name]
                y_f, _pm, s2, conv2 = transport_fori(
                    wS, supJ, capJ, 1 << 17, alpha=int(alpha),
                    refine_waves=8, eps0=int(e0),
                )
                ss_total += int(s2)
                assert bool(conv2)
                y_real = np.asarray(y_f, np.int64)[:, :M]
            obj = int((wA * y_real).sum())
            if obj_ref is None:
                obj_ref = obj
            flag = "" if obj == obj_ref else f"  OBJ DRIFT ({obj - obj_ref:+d})"
            print(f"  {strat:14s}: ss={ss_total}{flag}")


def instance_from_state(dev, st):
    """Rebuild (w[C,M], supply[C], col_cap[Mp]) the round core would
    solve from a fetched DeviceClusterState — mirrors round_core
    (scheduler/device_bulk.py) with a zero window offset. In preempt
    mode (round_core_preempt): supply = ALL live tasks, col_cap = total
    slots, and a 4th return carries the resident census R[C, M]."""
    import jax.numpy as jnp

    live = np.asarray(st["live"])
    pu = np.asarray(st["pu"])
    cls = np.asarray(st["cls"])
    M, P, S, C = dev.M, dev.P, dev.S, dev.C
    num_pus = dev.num_pus

    placed = live & (pu >= 0)
    machine = np.clip(pu, 0, num_pus - 1) // P
    census = np.zeros((M, C), np.int64)
    np.add.at(census, (machine[placed], cls[placed]), 1)

    pu_running = np.zeros(num_pus, np.int64)
    np.add.at(pu_running, pu[placed], 1)
    enabled = np.asarray(st["machine_enabled"])
    pu_free = np.where(np.repeat(enabled, P), S - pu_running, 0)
    machine_free = pu_free.reshape(M, P).sum(axis=1)

    cost_cm = np.asarray(dev.class_cost_fn(jnp.asarray(census))).astype(np.int64)
    w = cost_cm + dev.ec_cost - dev.unsched_cost

    col_cap = np.zeros(dev.Mp, np.int64)
    if dev.preemption:
        supply = np.bincount(cls[live], minlength=C)
        col_cap[:M] = np.where(enabled, P * S, 0)
        col_cap[-1] = supply.sum()
        R = np.zeros((C, M), np.int64)
        np.add.at(R, (cls[placed], machine[placed]), 1)
        return (w.astype(np.int32), supply.astype(np.int32),
                col_cap.astype(np.int32), R.astype(np.int32))

    unplaced = live & (pu < 0)
    W = dev.decode_width or dev.Tcap
    rows = np.nonzero(unplaced)[0][:W]
    supply = np.bincount(cls[rows], minlength=C)
    col_cap[:M] = machine_free
    col_cap[-1] = supply.sum()
    return w.astype(np.int32), supply.astype(np.int32), col_cap.astype(np.int32)


def replay(args) -> None:
    import jax.numpy as jnp

    from ksched_tpu.solver.layered import (
        _solve_transport,
        choose_eps0,
        default_eps0,
    )

    data = np.load(args.inst)
    n = int(data["n"])
    n_scale = int(data["n_scale"])
    Mp = int(data["Mp"])
    alphas = [int(a) for a in args.alpha.split(",")]
    refines = [int(r) for r in args.refine.split(",")]

    for k in range(n):
        w = data[f"w_{k}"].astype(np.int64)
        supply = data[f"supply_{k}"]
        col_cap = data[f"colcap_{k}"]
        orig = int(data[f"ss_{k}"])
        C, M = w.shape
        wP = np.zeros((C, Mp), np.int64)
        wP[:, :M] = w
        wS = jnp.asarray((wP * n_scale).astype(np.int32))
        sup = jnp.asarray(supply)
        cap = jnp.asarray(col_cap)
        eps_full = int(max(1, np.abs(wP).max() * n_scale))
        eps0 = int(
            choose_eps0(n_scale, eps_full, int(supply.sum()),
                        int(col_cap[:M].sum()))
        )
        print(f"instance {k}: C={C} M={M} supply={supply.tolist()} "
              f"cap_total={int(col_cap[:M].sum())} orig_ss={orig}")
        for alpha in alphas:
            for refine in refines:
                y, _pm, steps, conv = _solve_transport(
                    wS, sup, cap, jnp.int32(eps0), None,
                    alpha=alpha, max_supersteps=1 << 17,
                    refine_waves=refine,
                )
                obj = int(np.sum(np.asarray(y, np.int64)[:, :M] * wP[:, :M]))
                print(
                    f"  alpha={alpha} refine={refine}: "
                    f"ss={int(steps)} conv={bool(conv)} obj={obj}"
                )


def replay_tiered(args) -> None:
    """Re-solve captured PREEMPT (tiered) instances under eps0/refine
    sweeps — transport_fori_tiered outside the jitted round."""
    import jax.numpy as jnp

    from ksched_tpu.solver.layered import transport_fori_tiered

    data = np.load(args.inst)
    assert int(data["preempt"]) == 1, "not a preempt capture"
    n = int(data["n"])
    n_scale = int(data["n_scale"])
    Mp = int(data["Mp"])
    discount = int(data["discount"])
    refines = [int(r) for r in args.refine.split(",")]

    for k in range(n):
        w = data[f"w_{k}"].astype(np.int64)
        supply = data[f"supply_{k}"]
        col_cap = data[f"colcap_{k}"]
        R = data[f"residents_{k}"].astype(np.int64)
        orig = int(data[f"ss_{k}"])
        C, M = w.shape
        wHiP = np.zeros((C, Mp), np.int64)
        wHiP[:, :M] = w
        wLoP = wHiP.copy()
        wLoP[:, :M] -= discount
        RP = np.zeros((C, Mp), np.int64)
        RP[:, :M] = R
        wHi = jnp.asarray((wHiP * n_scale).astype(np.int32))
        wLo = jnp.asarray((wLoP * n_scale).astype(np.int32))
        RJ = jnp.asarray(RP.astype(np.int32))
        supJ = jnp.asarray(supply)
        capJ = jnp.asarray(col_cap)
        eps_full = int(max(1, np.abs(wHiP).max() * n_scale))
        print(f"inst {k}: C={C} total={int(supply.sum())} "
              f"residents={int(R.sum())} cap={int(col_cap[:M].sum())} "
              f"orig_ss={orig}")
        obj_ref = None
        for label, eps0 in [("full", eps_full), ("n", n_scale),
                            ("n/4", n_scale // 4), ("n/16", n_scale // 16)]:
            for rw in refines:
                y, _pm, steps, conv = transport_fori_tiered(
                    wLo, wHi, RJ, supJ, capJ, 1 << 17,
                    alpha=8, eps0=int(max(1, eps0)), refine_waves=rw,
                )
                yr = np.asarray(y, np.int64)[:, :M]
                ret = np.minimum(yr, R)
                obj = int((wHiP[:, :M] * yr).sum() - discount * ret.sum())
                if obj_ref is None:
                    obj_ref = obj
                drift = "" if obj == obj_ref else f"  OBJ {obj - obj_ref:+d}"
                print(f"  eps0={label:5s} refine={rw:2d}: ss={int(steps)} "
                      f"conv={bool(conv)}{drift}")


def main():
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)
    cap = sub.add_parser("capture")
    cap.add_argument(
        "--config", default="whare",
        choices=["whare", "coco", "quincy", "multiblock", "coco-preempt"],
    )
    cap.add_argument("--rounds", type=int, default=200)
    cap.add_argument("--warmup", type=int, default=0)
    cap.add_argument("--threshold", type=int, default=5000)
    cap.add_argument("--out", default="/tmp/tails.npz")
    cap.add_argument("--verbose", action="store_true")
    cap.set_defaults(fn=capture)
    rep = sub.add_parser("replay")
    rep.add_argument("--inst", default="/tmp/tails.npz")
    rep.add_argument("--alpha", default="2,8")
    rep.add_argument("--refine", default="8,32")
    rep.set_defaults(fn=replay)
    repg = sub.add_parser("replay-grouped")
    repg.add_argument("--inst", default="/tmp/tails_q.npz")
    repg.add_argument(
        "--strategies",
        default="two:1:256,two:n4:1024,full:n4:2,full:n:2",
        help="comma list: two:<eps0>:<budget> or full:<eps0>:<alpha>",
    )
    repg.set_defaults(fn=replay_grouped)
    rept = sub.add_parser("replay-tiered")
    rept.add_argument("--inst", default="/tmp/tails_preempt.npz")
    rept.add_argument("--refine", default="0,8")
    rept.set_defaults(fn=replay_tiered)
    args = ap.parse_args()
    args.fn(args)


if __name__ == "__main__":
    main()
