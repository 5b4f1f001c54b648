#!/usr/bin/env python
"""shard-smoke: the multi-chip rung's fixed-seed churn soak (CI gate).

Runs the SAME seeded churn scenario through three arms on a virtual
8-device CPU mesh (`make shard-smoke`, wired into `make verify`):

1. **scan-CSR reference** — single-chip JaxSolver (slot-stable plan,
   journal-scoped warm policy), device-resident mirror;
2. **sharded** — ShardedJaxSolver over the mesh, device-resident
   mirror in SHARDED plan mode (entry tables [D, Es], per-shard
   routed record scatters). Asserts, per round, placements
   BIT-IDENTICAL to arm 1; after warm-up every plan sync must be
   delta-sized ("delta"/"clean" — zero layout rebuilds, zero
   build_sharded_plan argsorts: the legacy plan cache stays empty);
3. **chaos** — the sharded rung at the top of the degradation ladder
   (sharded -> jax -> cpu_ref) under seeded solver-fault injection:
   every round must land (faults degrade, never crash), at least one
   degradation must actually fire, and a second identically-seeded
   run must produce bit-identical placements (containment +
   determinism, the chaos-smoke convention).

Exit code 0 = all assertions held.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

# env before jax import: hermetic CPU mesh, like tests/conftest.py
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def _build_arm(backend, machines, tasks, *, resident_mesh=None,
               plan_shards=None, seed=7):
    from ksched_tpu.drivers import add_job, build_cluster
    from ksched_tpu.graph.device_export import DeviceResidentState
    from ksched_tpu.utils import seed_rng

    seed_rng(seed)
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=machines, num_cores=1, pus_per_core=4,
        max_tasks_per_pu=4, backend=backend,
    )
    sched.solver.device_resident = True
    res = DeviceResidentState(sched.solver.state)
    if resident_mesh is not None:
        res.enable_sharded_plan(resident_mesh, "x")
    elif plan_shards is not None:
        # the single-chip REFERENCE arm consumes the SAME sharded-mode
        # layout the multi-chip arm maintains: every arm then sees one
        # entry order with one rebuild schedule, so the comparison is
        # pure single-chip-vs-mesh EXECUTION — layout-rebuild timing
        # (which legally re-sorts cost-tied optima) can't confound it
        sched.solver.state.plan.enable_sharding(plan_shards)
    sched.solver.resident = res
    job_id = add_job(sched, jmap, tmap, num_tasks=tasks)
    sched.schedule_all_jobs()
    return sched, jmap, tmap, job_id, res


def _drive_arm(label, backend, *, machines, tasks, rounds, warmup,
               resident_mesh=None, plan_shards=None, injector=None,
               verbose=False):
    """Run the seeded churn scenario; returns (placements per round,
    plan-kind counts post-warmup, scheduler, backend)."""
    from ksched_tpu.drivers.synthetic import add_task_to_job

    sched, jmap, tmap, job_id, res = _build_arm(
        backend, machines, tasks, resident_mesh=resident_mesh,
        plan_shards=plan_shards,
    )
    rng = np.random.default_rng(123)
    k = max(1, tasks // 12)
    placements = []
    kinds = {}
    rungs = {}
    waived = 0
    t0 = time.perf_counter()
    for r in range(rounds):
        if injector is not None:
            injector.begin_round(r)
        bound = sorted(sched.task_bindings.items())
        idx = sorted(
            int(x) for x in rng.choice(len(bound), k, replace=False)
        )
        for i in reversed(idx):
            sched.handle_task_completion(tmap.find(bound[i][0]))
        for _ in range(k):
            add_task_to_job(job_id, jmap, tmap, scheduler=sched)
        sched.add_job(jmap.find(job_id))
        gen0 = sched.solver.state.generation
        overflow0 = sched.solver.state.plan.region_overflows
        sched.schedule_all_jobs()
        placements.append({
            tmap.find(t).name: rid for t, rid in sched.task_bindings.items()
        })
        rung = getattr(backend, "last_rung_name", None)
        if rung is not None:
            rungs[rung] = rungs.get(rung, 0) + 1
        if r >= warmup:
            kind = res.last_plan_kind
            # the acceptance waives exactly the documented rebuild
            # triggers: pow2 bucket growth (generation moved) and
            # tail-pool exhaustion (region_overflows moved); any OTHER
            # rebuild after warm-up is a regression
            if kind == "rebuild":
                grew = sched.solver.state.generation != gen0
                overflowed = (
                    sched.solver.state.plan.region_overflows != overflow0
                )
                assert grew or overflowed, (
                    f"{label} round {r}: plan layout rebuilt outside "
                    "full_build / pow2 growth / pool exhaustion — "
                    "post-warm-up rounds must be delta-sized"
                )
                waived += 1
            else:
                kinds[kind] = kinds.get(kind, 0) + 1
        if verbose:
            print(
                f"# {label} round {r}: plan={res.last_plan_kind}",
                file=sys.stderr,
            )
    wall = time.perf_counter() - t0
    print(
        f"# {label}: {rounds} rounds in {wall:.1f}s, plan kinds {kinds}"
        + (f", {waived} growth-waived rebuild(s)" if waived else "")
    )
    return placements, kinds, sched, res, rungs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--machines", type=int, default=6)
    ap.add_argument("--tasks", type=int, default=48)
    ap.add_argument("--rounds", type=int, default=24)
    ap.add_argument("--warmup", type=int, default=4)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()

    import warnings

    import jax
    from jax.sharding import Mesh

    from ksched_tpu.parallel.sharded_solver import ShardedJaxSolver
    from ksched_tpu.runtime.chaos import ChaosPolicy, FaultInjector
    from ksched_tpu.runtime.degrade import build_degradation_ladder
    from ksched_tpu.solver.jax_solver import JaxSolver

    devs = jax.devices()
    assert len(devs) >= args.devices, (
        f"need {args.devices} virtual devices, got {len(devs)}"
    )
    mesh = Mesh(np.array(devs[: args.devices]), ("x",))
    common = dict(
        machines=args.machines, tasks=args.tasks,
        rounds=args.rounds, warmup=args.warmup, verbose=args.verbose,
    )

    # ---- arm 1: single-chip scan-CSR reference ----
    ref_pl, _, _, _, _ = _drive_arm(
        "scan-csr", JaxSolver(slot_stable=True, restart_budget=64),
        plan_shards=args.devices, **common,
    )

    # ---- arm 2: sharded, resident sharded plan mode ----
    sharded = ShardedJaxSolver(mesh)
    sh_pl, sh_kinds, sh_sched, sh_res, _ = _drive_arm(
        "sharded", sharded, resident_mesh=mesh, **common
    )
    for r, (a, b) in enumerate(zip(ref_pl, sh_pl)):
        assert a == b, (
            f"round {r}: sharded placements diverged from the scan-CSR "
            f"reference ({len(b)} vs {len(a)} bindings)"
        )
    assert sharded.last_path == "slot_stable", sharded.last_path
    assert sharded._plan is None, (
        "the legacy build_sharded_plan path ran — slot-stable rounds "
        "must never argsort a ShardedPlan"
    )
    assert sh_kinds.get("delta", 0) > 0, sh_kinds
    sh_res.parity_check()
    sh_res.plan_parity_check()
    print(
        f"# parity: {len(ref_pl)} rounds bit-identical; sharded plan "
        f"syncs post-warm-up: {sh_kinds}"
    )

    # ---- arm 3: chaos containment on the sharded rung ----
    def chaos_run():
        injector = FaultInjector(
            ChaosPolicy(seed=args.seed, solver_fault_prob=0.25)
        )
        ladder = build_degradation_ladder(
            ShardedJaxSolver(mesh), "sharded", injector=injector
        )
        assert ladder.rung_names() == ["sharded", "jax", "cpu_ref"]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            pl, _, _, _, rungs = _drive_arm(
                "chaos", ladder, injector=injector, **common
            )
        return pl, ladder.degradations_total, injector.snapshot(), rungs

    pl_a, degr_a, snap_a, rungs_a = chaos_run()
    pl_b, degr_b, snap_b, _rungs_b = chaos_run()
    assert degr_a > 0, "chaos arm drew no solver faults; raise the prob"
    # the containment LANDING matters, not just that degradations
    # fired: fault-free rounds land on the sharded rung, and a
    # sharded-rung fault must land on the JAX rung (a dead middle rung
    # would silently fall through to the cpu_ref oracle — the exact
    # regression a [D, Es]-shaped d_plan once caused here)
    assert rungs_a.get("sharded", 0) > 0, rungs_a
    assert rungs_a.get("jax", 0) > 0, (
        "no degraded round landed on the jax rung — the "
        "sharded -> jax containment rung is dead", rungs_a,
    )
    assert degr_a == degr_b and snap_a == snap_b, (
        "chaos runs drew different fault schedules"
    )
    for r, (a, b) in enumerate(zip(pl_a, pl_b)):
        assert a == b, f"round {r}: chaos arm not deterministic"
    print(
        f"# chaos containment: {degr_a} degradations off the sharded "
        f"rung, every round landed, twin runs bit-identical "
        f"(landing rungs: {rungs_a}; faults: {snap_a})"
    )
    print("shard-smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
