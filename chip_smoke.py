#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main paths once, through the entry points a user
would call, at the sizes BASELINE.json names, and checks what comes out
by the repo's own means. It is the only on-chip driver of the scanned
array replays, the kernels, the general and the sharded paths (the
benchmark, benchmarks/run.py, times the served paths, the array round
among them since PR 53); it states correctness facts and claims no
speed:

  served   cli's SchedulerService over SyntheticClusterAPI, 1,000 fake
           machines x 4 PUs x 4 tasks/PU, 10,000 podgen pods, `--backend
           jax --no-degrade` (scan-CSR on the chip), a cold round and a
           warm churn round; objective equal to `--backend native` (the
           C++ solver, the independent reference) on the same seeded
           input. Then round 1 again under `--backend auto`, recording
           which path answered. Then `--array-round` through the same
           `cli.build_service`, at the `array` phase's geometry: the
           fill and two rounds of arrivals and completions, every round
           converged and its Bindings at the optimum of the plain
           reference (benchmarks/reference_coco.py), the host's mirror
           equal to the device's table.
  array    DeviceBulkCluster at the coco50k geometry (50,000 tasks on
           1,000 machines x 4 PUs x 16 slots, decode width 1024): fill
           round + 3 x 32 steady rounds, tools/soak.py's invariants, and
           the same seeded rounds under set_pallas_mode("off"):
           placements, supersteps and pu_running identical (compiled
           Pallas kernel == XLA path, bit for bit).
  kernels  the plain and the tiered transport kernels called directly
           at the array path's [C, Mp] shape, each bit-equal to its XLA
           twin (the tiered one is not on the coco50k round's path).
  general  the __graft_entry__.entry() problem (10k x 1k, 131,072
           entries): scan-CSR converges in more than 0 supersteps.
  sharded  with >= 4 devices: dryrun_multichip(4) and one
           make_backend("sharded") solve bit-equal to the single-chip
           solve; otherwise `sharded: not_run (N device)`.
  resident the benchmark's two `trivial-10kx1k` deployments side by
           side: one service from benchmarks/configs/trivial-10kx1k.json's
           argv, one from trivial-10kx1k-resident.json's (`--device-resident
           --pipeline`), the same fill and the same batches through
           `run_round` (a trickle-shaped stretch of tens of pods with
           completions, a waves-shaped stretch of 1,000). Per round:
           each objective equals the native C++ solver's on
           `state.problem()`. At the end: the device mirror and the
           plan mirror equal the host's arrays, and the resident
           service compiled nothing after the first round of each
           stretch. Whether its Bindings are the synchronous loop's pod
           for pod is reported either way.
  antiaffinity  the benchmark's `k8s-5000-antiaffinity` deployment from
           its file's argv (5,000 machines x 110 slots, `--cost-model
           k8s_antiaffinity --backend jax`): the fill of 50,000 pods of
           16 workloads and 20 trickle-sized rounds with completions.
           Per round: every pod bound, the objective equal to the
           native C++ solver's on `state.problem()`. At the end: the
           replay of the Bindings and completions
           (benchmarks/reference_antiaffinity.check_anti_affinity) finds
           no node that ever held two pods of one workload, and nothing
           compiled after the first trickle round (in this leg, the
           zonespread and the quincy one: but in a round that re-fitted
           the slot plan, which runs the re-fitted programs itself;
           here that is the round in which the purge has taken the
           sixteen ECs' arcs).
  zonespread  the benchmark's `k8s-5000-zonespread` deployment from its
           file's argv (the same cluster in three zones, `--fake-zones 3
           --cost-model k8s_zonespread --backend jax`): the same fill and
           20 rounds on the graph two EC hops deep (task -> EC(g) ->
           ZONE(z) -> machine, the chain arcs capacity-bound). Per round
           as above. At the end: the replay
           (benchmarks/reference_zonespread.check_topology_spread, each
           node's zone from the label the service holds) finds no round
           that left a receiving zone more than maxSkew above the lowest,
           and nothing compiled after the first trickle round.
  preemption  the benchmark's `k8s-5000-preemption` deployment from its
           file's argv (5,000 machines x 4 slots, `--cost-model
           k8s_priority --preemption --backend jax`): the fill of
           20,000 low pods, exactly full, then rounds of high pods, each
           placed only by an eviction, the later ones with completions
           that hand evicted pods their slots back. Per round: the
           objective equal to the native C++ solver's on
           `state.problem()`, and the pods bound and evicted BY TIER
           equal to the plain reference's greedy
           (benchmarks/reference_preemption.reference_round) on the
           smoke's own books. At the end: the replay of the log
           (check_priority_preemption), no pod moved, no step down the
           ladder, and nothing compiled after the first trickle round.

  quincy   the benchmark's `gtrace-12500-quincy` deployment from its
           file's argv (12,500 machines x 12 slots in 250 racks,
           `--fake-racks 250 --cost-model quincy --backend jax`): the
           fill of 135,000 pods that read nothing and 20 trickle-sized
           rounds of pods that read blocks (`pods/quincy_blocks.py`),
           with completions. Per round: every pod bound, the objective
           equal to the native C++ solver's on `state.problem()`. At the
           end: the replay (benchmarks/reference_quincy.
           check_data_locality, each node's rack from the label the
           service holds) finds every round's Bindings at the optimum of
           the round's transportation problem, no step down the ladder,
           and nothing compiled after the first trickle round.

`--only PHASE` (repeatable) runs the named phases alone.

It refuses to start unless jax.devices()[0].platform == "tpu". No phase
is wrapped in a handler: a failure is a traceback and a non-zero exit.
RuntimeWarnings are errors (a degradation or a NOOP round warns).
`--rehearse-cpu` runs the same phases at tiny sizes under
set_pallas_mode("interpret"), says `cpu` on every line, and is the only
way the script runs without a chip.

The last line of stdout is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time
import warnings

import numpy as np

#: full sizes (BASELINE.json; the `array` phase's are BENCHMARK.json's
#: `coco-50kx1k` geometry) and the rehearsal's toys
FULL = dict(
    served=dict(machines=1_000, pods=10_000, churn=100),
    array=dict(tasks=50_000, machines=1_000, decode_width=1024, chunks=3, rounds=32),
    kernels=dict(classes=4, machines=1_000),
    general=dict(tasks=10_000, machines=1_000),
    resident=dict(scale=1, trickle=(26, 31, 12, 58, 40, 9, 60, 22), waves=4),
    antiaffinity=dict(scale=1, trickle=(30, 55, 12, 80, 41, 9, 64, 22, 50, 37) * 2),
    zonespread=dict(scale=1, trickle=(30, 55, 12, 80, 41, 9, 64, 22, 50, 37) * 2),
    preemption=dict(scale=1, trickle=(1, 30, 120, 55, 200, 9, 80, 0, 150, 41) * 2),
    quincy=dict(scale=1, trickle=(30, 55, 12, 80, 41, 9, 64, 22, 50, 37) * 2),
)
TINY = dict(
    served=dict(machines=20, pods=200, churn=10),
    array=dict(tasks=600, machines=12, decode_width=256, chunks=3, rounds=4),
    kernels=dict(classes=4, machines=40),
    general=dict(tasks=400, machines=40),
    resident=dict(scale=40, trickle=(2, 5, 1, 9, 3), waves=3),
    antiaffinity=dict(scale=40, trickle=(3, 6, 1, 9, 4)),
    zonespread=dict(scale=40, trickle=(3, 6, 1, 9, 4)),
    preemption=dict(scale=40, trickle=(1, 3, 12, 0, 6, 20)),
    quincy=dict(scale=40, trickle=(3, 6, 1, 9, 4)),
)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _table_is_the_mirror(svc, what: str) -> None:
    """The device's table of an `ArrayRoundService` against its host
    mirror and its own books: the live rows and their PUs are the
    mirror's, `pu_running` is a recount of the `pu` column, no PU holds
    more than its own slots (0 for a PU its machine does not have)."""
    st = svc.cluster.fetch_state()
    live, pu = np.asarray(st["live"]), np.asarray(st["pu"])
    running = np.asarray(st["pu_running"])
    check(set(np.flatnonzero(live).tolist()) == set(svc.row_of.values()),
          f"{what}: the mirror's rows are not the device's live rows")
    check(bool((np.where(live, pu, -1) == svc.pu_of_row).all()),
          f"{what}: the mirror's PUs are not the device's")
    check(bool((np.bincount(pu[live & (pu >= 0)], minlength=running.size) == running).all()),
          f"{what}: pu_running is not a recount of the pu column")
    check(bool((running <= svc.cluster.pu_slots).all()),
          f"{what}: a PU holds more than its own slots")


class Smoke:
    def __init__(self, rehearse: bool, seed: int) -> None:
        import jax

        from ksched_tpu.utils import device_stamp, enable_compile_cache

        self.rehearse = rehearse
        self.seed = seed
        self.sizes = TINY if rehearse else FULL
        self.cache_dir = enable_compile_cache()
        self.device = device_stamp()
        self.tag = self.device["platform"]
        #: persistent-cache traffic, from JAX's own monitoring events
        self.cache_events = {"requests": 0, "hits": 0, "writes": 0}
        names = {
            "/jax/compilation_cache/compile_requests_use_cache": "requests",
            "/jax/compilation_cache/cache_hits": "hits",
            "/jax/compilation_cache/cache_misses": "writes",
        }

        def on_event(event, **_kw):
            if event in names:
                self.cache_events[names[event]] += 1

        jax.monitoring.register_event_listener(on_event)
        # the walls of the run that filled the cache ride next to it, so
        # a later run prints cold and warm side by side
        self.walls_path = os.path.join(self.cache_dir, "chip_smoke_walls.json")
        self.walls_doc: dict = {}
        if os.path.exists(self.walls_path):
            with open(self.walls_path) as f:
                self.walls_doc = json.load(f)
        size = "tiny" if rehearse else "full"
        self.walls_key = f"{self.tag}x{self.device['count']}/{size}"
        self.cold_walls = self.walls_doc.get(self.walls_key)
        self.walls: dict = {}

    def say(self, text: str) -> None:
        print(f"chip_smoke[{self.tag}] {text}", flush=True)

    def phase(self, name: str, fn) -> None:
        """Run one phase. NOT wrapped in a handler: a failure ends the
        run with a traceback and a non-zero exit."""
        before = dict(self.cache_events)
        t0 = time.perf_counter()
        facts = fn()
        wall = time.perf_counter() - t0
        self.walls[name] = round(wall, 2)
        ev = {k: self.cache_events[k] - before[k] for k in before}
        if self.cold_walls is None:
            timing = f"cold_wall={wall:.1f}s"
        else:
            cold = self.cold_walls.get(name)
            timing = f"warm_wall={wall:.1f}s cold_wall={cold}s"
        self.say(
            f"phase {name}: pass {timing} compile_cache[requests={ev['requests']} "
            f"hits={ev['hits']} writes={ev['writes']}] {facts}"
        )

    def save_walls(self) -> None:
        if self.cold_walls is not None:
            return
        os.makedirs(self.cache_dir, exist_ok=True)
        self.walls_doc[self.walls_key] = self.walls
        with open(self.walls_path, "w") as f:
            json.dump(self.walls_doc, f)

    # -- served path -----------------------------------------------------

    def _serve(self, backend: str, rounds: int = 2) -> list:
        """The service exactly as cli.main builds it, driven round by
        round; returns one dict of facts per round."""
        from ksched_tpu import cli
        from ksched_tpu.cluster import SyntheticClusterAPI
        from ksched_tpu.cluster.api import PodEvent
        from ksched_tpu.utils import seed_rng

        sz = self.sizes["served"]
        seed_rng(self.seed)  # ids are drawn from the seeded RNG: same input per backend
        args = cli.build_arg_parser().parse_args([
            "--fake-machines", "--num-machines", str(sz["machines"]),
            "--pus-per-core", "4", "--max-tasks-per-pu", "4",
            "--pod-chan-size", str(sz["pods"] + sz["churn"]),
            "--podgen", str(sz["pods"]), "--one-shot",
            "--pod-batch-timeout", "0.2",
            "--backend", backend, "--no-degrade",
        ])
        api = SyntheticClusterAPI(pod_chan_size=args.pod_chan_size)
        svc = cli.build_service(args, api)
        check(svc.ladder is None, "--no-degrade must leave no degradation ladder")
        svc.init_topology(
            fake_machines=args.num_machines,
            cores_per_machine=args.cores_per_machine,
            pus_per_core=args.pus_per_core,
        )
        solver = svc.scheduler.solver
        out = []
        cli.podgen(api, args.podgen)
        for r in range(rounds):
            if r:
                # warm round: complete `churn` bound pods, admit as many
                for pod_id in sorted(api.bindings())[: sz["churn"]]:
                    check(svc.complete_pod(pod_id), f"{pod_id} was not bound")
                for i in range(sz["churn"]):
                    api.submit_pod(PodEvent(pod_id=f"late_{r}_{i}"))
            pods = api.get_pod_batch(args.pod_batch_timeout)
            want = sz["churn"] if r else sz["pods"]
            check(len(pods) == want, f"{len(pods)} pods arrived, {want} sent (channel dropped some)")
            t0 = time.perf_counter()
            bound = svc.run_round(pods)
            wall = time.perf_counter() - t0
            check(bound == len(pods), f"{backend} round {r + 1}: bound {bound} of {len(pods)}")
            check(svc.noop_rounds == 0, f"{backend} round {r + 1} was a NOOP round")
            out.append(dict(
                bound=bound,
                # the round's own (solver.last_result is a later solve's
                # in a round that re-fitted the slot plan)
                objective=int(svc.scheduler.last_timing.objective),
                supersteps=int(getattr(solver.backend, "last_supersteps", 0) or 0),
                refit=int(svc.scheduler.last_timing.plan_refits),
                path=getattr(solver.backend, "last_path", None),
                wall_s=round(wall, 2),
            ))
        total = sz["pods"] + (rounds - 1) * sz["churn"]
        check(len(api.bindings()) == total, f"{len(api.bindings())} bindings posted, want {total}")
        api.close()
        return out

    def served(self) -> str:
        jax_rounds = self._serve("jax")
        native_rounds = self._serve("native")
        for r, (j, n) in enumerate(zip(jax_rounds, native_rounds), 1):
            # a stalled scan-CSR solve raises (ladder off), so a round
            # that returns has converged; supersteps > 0 proves the
            # device program, not a host closed form, answered it. In a
            # round that re-fitted its slot plan (the fill) the count is
            # the later solve's, of the graph the round LEFT: every pod
            # bound and, since PR 52, every pin's unit routed to the sink
            # by the export, so nothing holds excess and it ends in 0
            check(
                j["supersteps"] > 0 or j["refit"],
                f"round {r}: scan-CSR ran 0 supersteps — the chip did nothing",
            )
            check(
                j["objective"] == n["objective"],
                f"round {r}: objective jax={j['objective']} != native={n['objective']}",
            )
        # what `--backend auto` does with the same stream: recorded for
        # S1, not asserted (finding 2 predicted "dense, 0 supersteps")
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            (auto,) = self._serve("auto", rounds=1)
        check(not caught, f"warnings under --backend auto: {[str(w.message) for w in caught]}")
        check(auto["objective"] == native_rounds[0]["objective"], "auto objective != native")
        check(
            any(j["supersteps"] > 0 for j in jax_rounds),
            "no served round ran a superstep on the device",
        )
        sz = self.sizes["served"]
        return (
            f"machines={sz['machines']} pods={sz['pods']} backend=jax/no-degrade "
            f"round1[bound={jax_rounds[0]['bound']} supersteps={jax_rounds[0]['supersteps']} "
            f"objective={jax_rounds[0]['objective']}==native wall={jax_rounds[0]['wall_s']}s] "
            f"round2[bound={jax_rounds[1]['bound']} supersteps={jax_rounds[1]['supersteps']} "
            f"objective={jax_rounds[1]['objective']}==native wall={jax_rounds[1]['wall_s']}s] "
            f"noop_rounds=0 | backend=auto round1[last_path={auto['path']} "
            f"supersteps={auto['supersteps']}] | {self._serve_array()}"
        )

    def _serve_array(self) -> str:
        """`--array-round` through `cli.build_service` at the `array` leg's
        geometry: the fill and two served rounds with arrivals and
        completions; every round converged, its Bindings at the plain
        reference's optimum on a census kept from the Bindings alone, and
        the device's table equal to the service's mirror at the end."""
        from benchmarks.reference_coco import cost_matrix, reference_round
        from ksched_tpu import cli
        from ksched_tpu.cluster import SyntheticClusterAPI
        from ksched_tpu.cluster.api import PodEvent

        sz = self.sizes["array"]
        tasks, machines = sz["tasks"], sz["machines"]
        churn = max(8, tasks // 100)
        args = cli.build_arg_parser().parse_args([
            "--fake-machines", "--num-machines", str(machines), "--pus-per-core", "4",
            "--max-tasks-per-pu", "16", "--cost-model", "coco", "--array-round",
            "--pod-chan-size", str(tasks + churn), "--pod-batch-timeout", "0.2",
        ])
        api = SyntheticClusterAPI(pod_chan_size=args.pod_chan_size)
        svc = cli.build_service(args, api)
        svc.init_topology(
            fake_machines=machines, cores_per_machine=args.cores_per_machine,
            pus_per_core=args.pus_per_core,
        )
        rng = np.random.default_rng(self.seed + 2)
        census = np.zeros((machines, 4), np.int64)
        class_of, where, walls, costs = {}, {}, [], []
        seen: set = set()  # pods whose Binding an earlier round posted
        for r in range(3):
            n = churn if r else tasks
            if r:
                for pod in sorted(where)[:churn]:
                    check(svc.complete_pod(pod), f"array service: {pod} was not bound")
                    census[where.pop(pod), class_of[pod]] -= 1
            pods = [PodEvent(f"a{r}_{i}", task_class=int(c))
                    for i, c in enumerate(rng.integers(0, 4, n))]
            class_of.update((p.pod_id, p.task_class) for p in pods)
            before = len(api.bindings())
            t0 = time.perf_counter()
            bound = svc.run_round(pods)
            walls.append(round(time.perf_counter() - t0, 3))
            check(bound == n, f"array service round {r + 1}: bound {bound} of {n}")
            check(svc.unconverged_rounds == 0, f"array service round {r + 1} did not converge")
            new = {pod: node for pod, node in api.bindings().items() if pod not in seen}
            seen.update(new)
            check(len(new) == n and len(api.bindings()) == before + n,
                  f"array service round {r + 1}: {len(new)} Bindings posted, want {n}")
            at = {pod: int(node.rsplit("_", 1)[1]) for pod, node in new.items()}
            cost = cost_matrix(census)
            served = sum(int(cost[class_of[pod], m]) for pod, m in at.items())
            want = reference_round(np.bincount([class_of[p] for p in at], minlength=4), census, 64)
            check(served == want, f"array service round {r + 1}: Bindings cost {served}, optimum {want}")
            where.update(at)
            for pod, m in at.items():
                census[m, class_of[pod]] += 1
            costs.append(served)
        svc.flush_pending_bindings()
        _table_is_the_mirror(svc, "array service")
        api.close()
        return (
            f"array-round[tasks={tasks} machines={machines}x4x16 rows={svc.cluster.Tcap} "
            f"fill+2x{churn}: every round converged, cost=={costs} the reference's optimum, "
            f"mirror==table, walls={walls}s]"
        )

    # -- array path --------------------------------------------------------

    def _array_run(self, pallas_mode: str) -> dict:
        """Fill + chunks x rounds steady rounds at the coco50k geometry
        on a fresh cluster traced under `pallas_mode` (read at trace
        time); soak's invariants after every chunk."""
        import jax

        from ksched_tpu.costmodels import coco
        from ksched_tpu.costmodels.device_costs import coco_device_cost_fn
        from ksched_tpu.ops import get_pallas_mode, set_pallas_mode
        from ksched_tpu.scheduler.device_bulk import DeviceBulkCluster
        from ksched_tpu.utils import next_pow2

        sz = self.sizes["array"]
        tasks, machines = sz["tasks"], sz["machines"]
        rng = np.random.default_rng(self.seed)
        penalties = rng.integers(0, 40, (machines, 4)).astype(np.int64)
        prev = get_pallas_mode()
        set_pallas_mode(pallas_mode)
        try:
            dev = DeviceBulkCluster(
                num_machines=machines, pus_per_machine=4, slots_per_pu=16,
                num_jobs=20, num_task_classes=4,
                task_capacity=next_pow2(tasks + 4096),
                class_cost_fn=coco_device_cost_fn(penalties),
                unsched_cost=coco.UNSCHEDULED_COST, ec_cost=0,
                supersteps=1 << 17, decode_width=sz["decode_width"],
            )
            dev.add_tasks(
                tasks,
                rng.integers(0, 20, tasks).astype(np.int32),
                rng.integers(0, 4, tasks).astype(np.int32),
            )
            fill = dev.fetch_stats(dev.round())
            check(bool(np.all(fill["converged"])), "fill round did not converge")
            supersteps = [np.atleast_1d(fill["supersteps"])]
            churn_n = max(1, tasks // 100)
            for c in range(sz["chunks"]):
                got = dev.fetch_stats(
                    dev.run_steady_rounds(sz["rounds"], 0.01, churn_n, seed=100 + c)
                )
                check(bool(got["converged"].all()), f"non-convergence in chunk {c}")
                check(
                    bool((got["supersteps"] > 0).all()),
                    f"chunk {c}: a steady round ran 0 supersteps",
                )
                supersteps.append(got["supersteps"])
                # the invariants tools/soak.py checks at every chunk
                st = dev.fetch_state()
                live, pu = np.asarray(st["live"]), np.asarray(st["pu"])
                placed = live & (pu >= 0)
                running = np.asarray(st["pu_running"])
                recount = np.bincount(pu[placed], minlength=dev.num_pus)
                check(bool((recount == running).all()), f"pu_running drift in chunk {c}")
                check(bool((running <= dev.S).all()), f"slot overflow in chunk {c}")
                enabled = np.asarray(st["machine_enabled"])
                on_disabled = placed & ~np.repeat(enabled, dev.P)[np.clip(pu, 0, dev.num_pus - 1)]
                check(not on_disabled.any(), f"task on a disabled machine in chunk {c}")
            jax.block_until_ready(dev.state)
        finally:
            set_pallas_mode(prev)
        return dict(
            pu=pu, live=live, pu_running=running,
            supersteps=np.concatenate(supersteps),
            placed=int(placed.sum()),
        )

    def _array_three_types(self) -> str:
        """The layout `gtrace-12500-wharemap-array` runs: `--array-round
        --cost-model whare` on machines of three types (2 / 4 / 8 PUs in
        one table padded to 8), the fill to nine tenths and two rounds of
        arrivals and completions. Every round converged and at the plain
        reference's optimum (benchmarks/reference_wharemap_array.py), the
        device's table equal to the books: the mirror's rows and PUs,
        `pu_running` a recount, no PU above its own slots (0 for a PU its
        machine does not have)."""
        from benchmarks.reference_wharemap_array import check_interference_map_array
        from ksched_tpu import cli
        from ksched_tpu.cluster import SyntheticClusterAPI
        from ksched_tpu.cluster.api import PodEvent

        machines = max(40, self.sizes["array"]["machines"])
        types = "A:1:10,B:2:930,C:4:60"
        args = cli.build_arg_parser().parse_args([
            "--fake-machines", "--num-machines", str(machines), "--pus-per-core", "2",
            "--max-tasks-per-pu", "3", "--fake-machine-types", types, "--cost-model", "whare",
            "--array-round", "--pod-chan-size", "200000", "--pod-batch-timeout", "0.2",
        ])
        api = SyntheticClusterAPI(pod_chan_size=args.pod_chan_size)
        svc = cli.build_service(args, api)
        svc.init_topology(fake_machines=machines, pus_per_core=args.pus_per_core)
        slots = int(svc.machine_slots.sum())
        rng = np.random.default_rng(self.seed + 3)
        class_of, log, seen, batches = {}, [], set(), []
        churn = max(6, slots // 200)
        for r, n in enumerate((slots * 9 // 10, churn, churn)):
            if r:
                for pod in sorted(seen)[(r - 1) * churn: r * churn]:
                    check(svc.complete_pod(pod), f"three types: {pod} was not bound")
                    log.append(("done", pod, "", float(r)))
            pods = [PodEvent(f"t{r}_{i}", task_class=int(c))
                    for i, c in enumerate(rng.integers(0, 4, n))]
            class_of.update((p.pod_id, p.task_class) for p in pods)
            batches.append((r + 0.5, [p.pod_id for p in pods]))
            check(svc.run_round(pods) == n, f"three types, round {r + 1}: not every pod bound")
            new = {pod: node for pod, node in api.bindings().items() if pod not in seen}
            seen.update(new)
            log += [("bind", pod, node, r + 0.5) for pod, node in new.items()]
        check(svc.unconverged_rounds == 0 and svc.cost_overflows == 0,
              "three types: a round did not converge, or its costs overflowed")
        faults, facts = check_interference_map_array(
            log, class_of, svc.nodes, cli.parse_machine_types(types), args.pus_per_core,
            args.max_tasks_per_pu, batches=batches,
        )
        check(not faults, f"three types: {faults}")
        svc.flush_pending_bindings()
        _table_is_the_mirror(svc, "three types")
        api.close()
        by_pus = np.bincount((svc.cluster.pu_slots.reshape(machines, -1) > 0).sum(axis=1)).tolist()
        return (
            f"three-types[whare machines={machines} by PUs={by_pus} slots={slots} rows={svc.cluster.Tcap} "
            f"fill+2x{churn}: 0 unconverged, cost {facts['served_cost']}=={facts['optimum_cost']} the "
            f"reference's optimum, table==books]"
        )

    def array(self) -> str:
        kernel_mode = "interpret" if self.rehearse else "on"
        a = self._array_run(kernel_mode)
        b = self._array_run("off")
        for key in ("pu", "live", "pu_running", "supersteps"):
            check(
                np.array_equal(a[key], b[key]),
                f"pallas({kernel_mode}) and XLA paths disagree on {key}",
            )
        sz = self.sizes["array"]
        steady = a["supersteps"][1:]
        return (
            f"tasks={sz['tasks']} machines={sz['machines']}x4x16 classes=4 "
            f"decode_width={sz['decode_width']} rounds=1+{sz['chunks']}x{sz['rounds']} "
            f"all converged, invariants hold, placed={a['placed']} "
            f"supersteps[fill={int(a['supersteps'][0])} steady p50={int(np.median(steady))} "
            f"min={int(steady.min())} max={int(steady.max())}] "
            f"pallas({kernel_mode})==xla: placements, supersteps, pu_running identical | "
            f"{self._array_three_types()}"
        )

    # -- the two transport kernels, directly -----------------------------

    def kernels(self) -> str:
        import jax.numpy as jnp

        from ksched_tpu.ops.transport_pallas import (
            transport_loop_pallas,
            transport_loop_pallas_tiered,
        )
        from ksched_tpu.solver.layered import (
            _transport_loop,
            _transport_loop_tiered,
            pad_geometry,
        )

        sz = self.sizes["kernels"]
        C, M = sz["classes"], sz["machines"]
        rng = np.random.default_rng(self.seed + 1)
        Mp, n_scale = pad_geometry(M, C)
        wS = np.zeros((C, Mp), np.int32)
        wS[:, :M] = rng.integers(-30, 30, (C, M)) * n_scale
        supply = rng.integers(0, 60 * max(1, M // 40), C).astype(np.int32)
        col_cap = np.zeros(Mp, np.int32)
        col_cap[:M] = rng.integers(0, 6, M)
        col_cap[-1] = supply.sum()
        wLo = wS.copy()
        wLo[:, :M] -= 5 * n_scale
        res = rng.integers(0, 6, (C, Mp)).astype(np.int32)
        res[:, -1] = 0
        eps0 = jnp.asarray(np.int32(max(1, np.abs(wS).max())))
        wS_d, wLo_d, sup_d, cap_d = map(jnp.asarray, (wS, wLo, supply, col_cap))
        U = jnp.minimum(sup_d[:, None], cap_d[None, :])
        R = jnp.minimum(jnp.asarray(res), U)
        interpret = self.rehearse  # by name, never inferred

        y_x, _z, pm_x, s_x, c_x = _transport_loop(wS_d, U, sup_d, cap_d, eps0, 8, 50_000)
        y_p, pm_p, s_p, c_p = transport_loop_pallas(
            wS_d, sup_d, cap_d, eps0, alpha=8, max_supersteps=50_000, interpret=interpret,
        )
        check(bool(c_x) and bool(c_p), "plain transport did not converge")
        check(int(s_x) == int(s_p), f"plain transport supersteps {int(s_p)} != xla {int(s_x)}")
        check(np.array_equal(y_x, y_p) and np.array_equal(pm_x, pm_p), "plain transport kernel != xla")

        y_x, _z, pm_x, t_x, c_x = _transport_loop_tiered(
            wLo_d, wS_d, R, U, sup_d, cap_d, eps0, 8, 50_000, refine_waves=8,
        )
        y_p, pm_p, t_p, c_p = transport_loop_pallas_tiered(
            wLo_d, wS_d, jnp.asarray(res), sup_d, cap_d, eps0,
            alpha=8, max_supersteps=50_000, interpret=interpret, refine_waves=8,
        )
        check(bool(c_x) and bool(c_p), "tiered transport did not converge")
        check(int(t_x) == int(t_p), f"tiered transport supersteps {int(t_p)} != xla {int(t_x)}")
        check(np.array_equal(y_x, y_p) and np.array_equal(pm_x, pm_p), "tiered transport kernel != xla")
        mode = "interpret" if interpret else "compiled"
        return (
            f"[C={C}, Mp={Mp}] transport({mode})==xla supersteps={int(s_p)}; "
            f"tiered({mode})==xla supersteps={int(t_p)}"
        )

    # -- general graph -----------------------------------------------------

    def general(self) -> str:
        import jax

        import __graft_entry__ as graft

        sz = self.sizes["general"]  # full: the driver's own entry() problem
        fn, args = graft.entry(num_machines=sz["machines"], tasks=sz["tasks"])
        _flow, steps, converged = jax.jit(fn)(*args)
        check(bool(converged), f"scan-CSR did not converge ({int(steps)} supersteps)")
        check(int(steps) > 0, "scan-CSR ran 0 supersteps")
        entries = 2 * int(args[0].shape[0])
        return f"entries={entries} scan-CSR converged supersteps={int(steps)}"

    # -- several chips -------------------------------------------------------

    def sharded(self) -> str:
        import __graft_entry__ as graft
        from ksched_tpu.solver.select import make_backend

        count = self.device["count"]
        if count < 4:
            return f"sharded: not_run ({count} device)"
        with contextlib.redirect_stdout(io.StringIO()) as printed:
            graft.dryrun_multichip(4)
        self.say(printed.getvalue().strip())
        sz = self.sizes["general"]
        problem = graft._build_problem(num_machines=sz["machines"], tasks=sz["tasks"])
        many = make_backend("sharded", warm_start=False).solve(problem)
        one = make_backend("jax", warm_start=False).solve(problem)
        check(many.objective == one.objective, "sharded objective != single-chip")
        check(many.iterations == one.iterations, "sharded supersteps != single-chip")
        check(np.array_equal(many.flow, one.flow), "sharded flows != single-chip flows")
        return (
            f"dryrun_multichip(4) ok; make_backend('sharded') over {count} devices "
            f"bit-equal to single-chip scan-CSR at {sz['tasks']}x{sz['machines']} "
            f"(objective={one.objective}, supersteps={one.iterations})"
        )

    # -- the resident deployment against its synchronous control ------------

    def _config_service(self, name: str, scale: int, api_cls):
        """One benchmark deployment at 1/scale, built from its file's
        argv as cli.main builds it: (config, args, api, service)."""
        from ksched_tpu import cli
        from ksched_tpu.utils import seed_rng

        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "benchmarks", "configs", name + ".json")) as f:
            config = json.load(f)
        argv = list(config["argv"])
        i = argv.index("--num-machines") + 1
        argv[i] = str(int(argv[i]) // scale)
        seed_rng(self.seed)
        args = cli.build_arg_parser().parse_args(argv)
        api = api_cls(pod_chan_size=args.pod_chan_size)
        svc = cli.build_service(args, api)
        svc.init_topology(
            fake_machines=args.num_machines,
            cores_per_machine=args.cores_per_machine,
            pus_per_core=args.pus_per_core,
        )
        return config, args, api, svc

    @staticmethod
    def _compile_events() -> list:
        """A list that grows by one with every program JAX compiles."""
        import jax

        compiles: list = []

        def on_duration(event, _duration, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(event)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        return compiles

    def _serve_config(self, name: str, compiles: list) -> dict:
        """One benchmark deployment, driven through the leg's fixed
        batches."""
        from ksched_tpu.cluster import SyntheticClusterAPI
        from ksched_tpu.cluster.api import PodEvent
        from ksched_tpu.solver.select import make_backend

        sz = self.sizes["resident"]
        config, _args, api, svc = self._config_service(name, sz["scale"], SyntheticClusterAPI)
        fill = config["resident_pods"] // sz["scale"]
        wave = config["wave_pods"] // sz["scale"]
        solver = svc.scheduler.solver
        rung = solver.backend.primary if svc.ladder is not None else solver.backend
        native = make_backend("native", warm_start=False, fallback=False)
        live: list = []
        rounds: list = []
        late = {"trickle": 0, "waves": 0}  # compiles after a stretch's first round
        plan = [("fill", fill, 0)]
        plan += [("trickle", k, k) for k in sz["trickle"]]
        plan += [("waves", wave, wave)] * sz["waves"]
        seen = ""
        for stretch, arrivals, completions in plan:
            for pod_id in live[:completions]:  # the oldest complete first
                check(svc.complete_pod(pod_id), f"{name}: {pod_id} was not bound")
            del live[:completions]
            for _ in range(arrivals):
                live.append(f"pod_{len(rounds)}_{len(live)}")
                api.submit_pod(PodEvent(pod_id=live[-1]))
            pods = api.get_pod_batch(0.2)
            check(len(pods) == arrivals, f"{name}: {len(pods)} pods arrived, {arrivals} sent")
            before, mark = api.bindings(), len(compiles)
            t0 = time.perf_counter()
            bound = svc.run_round(pods)
            wall = time.perf_counter() - t0
            svc.run_round([], solve=False)  # the idle sweep POSTs what a pipeline deferred
            if stretch == seen:
                late[stretch] += len(compiles) - mark
            seen = stretch
            check(bound == arrivals, f"{name} {stretch}: bound {bound} of {arrivals}")
            check(svc.noop_rounds == 0, f"{name} {stretch}: a NOOP round")
            ours = int(solver.last_result.objective)
            theirs = int(native.solve(solver.state.problem()).objective)
            check(ours == theirs, f"{name} {stretch} round {len(rounds)}: objective {ours} != native {theirs}")
            res = solver.resident
            rounds.append(dict(
                stretch=stretch, wall_ms=round(wall * 1e3, 1), objective=ours,
                new={p: n for p, n in api.bindings().items() if before.get(p) != n},
                supersteps=int(rung.last_supersteps), scope=rung.last_warm_scope,
                upload=(res.last_upload_kind, res.last_record_bucket, res.last_plan_kind,
                        res.last_plan_bucket, res.last_upload_bytes,
                        res.last_plan_relocations) if res is not None else None,
            ))
        check(svc.ladder is None or svc.ladder.degradations_total == 0, f"{name}: a step down the ladder")
        if solver.resident is not None:
            solver.resident.parity_check()
            solver.resident.plan_parity_check()
        api.close()
        return dict(rounds=rounds, late=late, state=solver.state)

    def resident(self) -> str:
        compiles = self._compile_events()
        sync = self._serve_config("trivial-10kx1k", compiles)
        res = self._serve_config("trivial-10kx1k-resident", compiles)
        check(res["late"] == {"trickle": 0, "waves": 0},
              f"the resident service compiled after a stretch's first round: {res['late']}")
        pairs = list(zip(sync["rounds"], res["rounds"]))
        check(all(a["objective"] == b["objective"] for a, b in pairs),
              "resident and synchronous objectives differ (both equal native's: the inputs differ)")
        differ = [i for i, (a, b) in enumerate(pairs) if a["new"] != b["new"]]
        for i, r in enumerate(res["rounds"]):
            kind, bucket, plan_kind, plan_bucket, nbytes, moved = r["upload"]
            self.say(
                f"resident round {i} {r['stretch']}: wall_ms sync={sync['rounds'][i]['wall_ms']} "
                f"resident={r['wall_ms']} supersteps sync={sync['rounds'][i]['supersteps']} "
                f"resident={r['supersteps']} scope={r['scope']} upload={kind}/{bucket} "
                f"plan={plan_kind}/{plan_bucket} bytes={nbytes} relocations={moved}"
            )
        st = res["state"]
        return (
            f"nodes={st.n_cap} arcs={st.m_cap} entries={st.plan.entry_cap} "
            f"rounds={len(pairs)} objectives==native in both; mirror and plan mirror equal the host's; "
            f"compiles after a stretch's first round: resident={res['late']} synchronous={sync['late']}; "
            f"bindings_identical={not differ}"
            + (f" (first differing round {differ[0]}, {len(differ)} in all)" if differ else "")
            + f"; warm scopes resident={sorted(set(r['scope'] for r in res['rounds']))}"
        )


    # -- the placement rules, at the benchmark's size -----------------------

    def _serve_rule(self, phase: str, name: str, counters, pod_maker=None) -> dict:
        """A benchmark deployment whose pods carry a placement rule, as
        cli.main builds it: the fill and trickle-sized rounds (as many of
        the oldest pods complete as arrive), every round's objective
        against native C++ on the same problem; `counters` are the
        RoundTiming fields each round's line shows. A pod carries its
        class alone, or what `pod_maker(config, args)` makes of (round,
        pod id, class). Returns what the phase's replay needs."""
        from benchmarks.client import BenchClusterAPI
        from ksched_tpu.cluster.api import PodEvent
        from ksched_tpu.solver.select import make_backend

        compiles = self._compile_events()
        sz = self.sizes[phase]
        config, args, api, svc = self._config_service(name, sz["scale"], BenchClusterAPI)
        api.svc = svc
        if pod_maker is None:
            make = lambda _r, pod, c: PodEvent(pod_id=pod, task_class=c)  # noqa: E731
        else:
            make = pod_maker(config, args)
        rng = np.random.default_rng([self.seed, 30])
        solver = svc.scheduler.solver
        rung = solver.backend.primary if svc.ladder is not None else solver.backend
        native = make_backend("native", warm_start=False, fallback=False)
        group_of: dict = {}
        live: list = []
        late = 0  # compiles after the first trickle round
        plan = [config["resident_pods"] // sz["scale"], *sz["trickle"]]
        for r, arrivals in enumerate(plan):
            completions = arrivals if r else 0
            api.complete_later(live[:completions])  # the oldest complete first
            del live[:completions]
            for _ in range(arrivals):
                pod = f"pod_{len(group_of)}"
                group_of[pod] = int(rng.integers(0, config["task_classes"]))
                live.append(pod)
                api.submit_pod(make(r, pod, group_of[pod]))
            pods = api.poll_pod_batch(0.2)
            check(len(pods) == arrivals, f"{name}: {len(pods)} pods arrived, {arrivals} sent")
            mark = len(compiles)
            t0 = time.perf_counter()
            bound = svc.run_round(pods)
            wall = time.perf_counter() - t0
            t = svc.scheduler.last_timing
            # a round that re-fits the slot plan runs the re-fitted
            # programs itself (FlowScheduler._refit_plan) so that the
            # next round compiles nothing: its compiles are the re-fit's
            if r > 1 and not t.plan_refits:
                late += len(compiles) - mark
            check(bound == arrivals, f"{name} round {r}: bound {bound} of {arrivals}")
            check(api.completions_refused == 0, f"{name} round {r}: a completion was refused")
            check(svc.noop_rounds == 0, f"{name} round {r}: a NOOP round")
            ours = int(solver.last_result.objective)
            theirs = int(native.solve(solver.state.problem()).objective)
            check(ours == theirs, f"{name} round {r}: objective {ours} != native {theirs}")
            self.say(
                f"{phase} round {r}: pods={arrivals} wall_ms={wall * 1e3:.1f} "
                f"graph_update_ms={t.graph_update_s * 1e3:.1f} solve_ms={t.solve_s * 1e3:.1f} "
                f"supersteps={int(rung.last_supersteps)} objective={ours} "
                f"plan_rows={t.plan_rows} plan_refits={t.plan_refits} "
                + " ".join(f"{c}={getattr(t, c)}" for c in counters)
            )
        check(svc.ladder is None or svc.ladder.degradations_total == 0, f"{name}: a step down the ladder")
        check(
            late == 0,
            f"{name}: {late} programs compiled after the first trickle round, outside a round that re-fitted the plan",
        )
        api.close()
        st = solver.state
        return dict(
            config=config, svc=svc, log=api.log, polls=api.polls, group_of=group_of, late=late,
            shapes=f"machines={args.num_machines} nodes={st.n_cap} arcs={st.m_cap} "
            f"entries={st.plan.entry_cap} rounds={len(plan)} objectives==native in every round",
        )

    def antiaffinity(self) -> str:
        """`k8s-5000-antiaffinity`: the served rounds, and the replay of
        the Binding log against the rule."""
        from benchmarks.reference_antiaffinity import check_anti_affinity

        name = "k8s-5000-antiaffinity"
        run = self._serve_rule(
            "antiaffinity", name, ("ec_nodes", "ec_arcs", "ec_arcs_changed", "unscheduled_by_rule")
        )
        fault = check_anti_affinity(run["log"], run["group_of"])
        check(fault is None, f"{name}: {fault}")
        return (
            f"{run['shapes']}; {len(run['log'])} Bindings and completions replayed: "
            f"no node held two pods of a workload; "
            f"compiles after the first trickle round: {run['late']}"
        )

    def zonespread(self) -> str:
        """`k8s-5000-zonespread`: the served rounds on the graph two EC
        hops deep, and the replay of the Binding log against the rule,
        each node's zone from the label the service holds."""
        from benchmarks.reference_zonespread import check_topology_spread
        from ksched_tpu.data import ZONE_LABEL

        name = "k8s-5000-zonespread"
        run = self._serve_rule(
            "zonespread", name,
            ("ec_nodes", "ec_arcs", "ec_arcs_changed", "ec_chain_arcs_changed",
             "spread_fallback", "unscheduled_by_rule"),
        )
        svc, config = run["svc"], run["config"]
        label_of = {
            node: svc.resource_map.find(machine).descriptor.labels[ZONE_LABEL]
            for node, machine in svc.node_to_machine.items()
        }
        zones = sorted(set(label_of.values()))
        check(len(zones) == config["zones"], f"{name}: zones {zones}, the file says {config['zones']}")
        zone_of = {node: zones.index(label) for node, label in label_of.items()}
        fault, facts = check_topology_spread(run["log"], run["group_of"], zone_of, config["max_skew"])
        check(fault is None, f"{name}: {fault}")
        return (
            f"{run['shapes']}; {facts['replayed']} Bindings and completions replayed over "
            f"{facts['rounds']} rounds in {len(zones)} zones: largest skew {facts['largest_skew']} "
            f"(maxSkew {config['max_skew']}); compiles after the first trickle round: {run['late']}"
        )


    def quincy(self) -> str:
        """`gtrace-12500-quincy`: the served rounds under Quincy's
        policy with its rack tier, and the replay of the Binding log
        against the plain reference's optimum of every round, each
        node's rack from the label the service holds."""
        from benchmarks.pods.quincy_blocks import blocks_of
        from benchmarks.reference_quincy import check_data_locality
        from ksched_tpu.cluster.api import PodEvent
        from ksched_tpu.data import RACK_LABEL

        name = "gtrace-12500-quincy"
        inputs_of: dict = {}

        def pod_maker(config, args):
            # the cluster the pods' blocks lie in is the one that was built
            argv = list(config["argv"])
            argv[argv.index("--num-machines") + 1] = str(args.num_machines)
            built = dict(config, argv=argv)

            def make(r, pod, c):
                # the fill reads nothing, as the benchmark's resident pods
                inputs_of[pod] = blocks_of(pod, built, self.seed) if r else ()
                return PodEvent(pod_id=pod, task_class=c, inputs=inputs_of[pod])

            return make

        run = self._serve_rule(
            "quincy", name,
            ("pref_arcs_live", "pref_arcs_changed", "ec_chain_arcs_changed", "ec_arcs_changed",
             "bound_via_machine", "bound_via_rack", "bound_via_cluster", "unscheduled_by_rule"),
            pod_maker=pod_maker,
        )
        svc, config = run["svc"], run["config"]
        rack_of = {
            node: svc.resource_map.find(machine).descriptor.labels[RACK_LABEL]
            for node, machine in svc.node_to_machine.items()
        }
        racks = len(set(rack_of.values()))
        check(racks == min(config["racks"], len(rack_of)), f"{name}: {racks} racks, the file says {config['racks']}")
        faults, facts = check_data_locality(
            run["log"], inputs_of, rack_of, svc.max_tasks_per_pu,  # 1 core x 1 PU a node
            admitted=[(t1, n) for _t0, t1, n in run["polls"] if n],
        )
        check(not faults, f"{name}: {faults}")
        check(facts["rounds_compared"] == facts["rounds"], f"{name}: a round was short of room")
        return (
            f"{run['shapes']}; {facts['replayed']} Bindings and completions replayed over "
            f"{facts['rounds']} rounds in {racks} racks: served cost {facts['served_cost']} == "
            f"optimum {facts['optimum_cost']}, bound through a machine / rack / X arc "
            f"{facts['bound_via']}, remote bytes {facts['remote_bytes_share']:.1f}%; "
            f"compiles after the first trickle round: {run['late']}"
        )

    def preemption(self) -> str:
        """`k8s-5000-preemption`: the first served rounds with
        `--preemption` on the chip, against native C++ and the plain
        reference's greedy, tier by tier."""
        from benchmarks.client import BenchClusterAPI
        from benchmarks.reference_preemption import check_priority_preemption, reference_round
        from ksched_tpu.cluster.api import PodEvent
        from ksched_tpu.solver.select import make_backend

        name = "k8s-5000-preemption"
        compiles = self._compile_events()
        sz = self.sizes["preemption"]
        config, args, api, svc = self._config_service(name, sz["scale"], BenchClusterAPI)
        api.svc = svc
        solver = svc.scheduler.solver
        rung = solver.backend.primary
        native = make_backend("native", warm_start=False, fallback=False)
        capacity = args.cores_per_machine * args.pus_per_core * args.max_tasks_per_pu
        total = args.num_machines * capacity
        low, high = config["priority_by_role"]["fill"], config["priority_by_role"]["measured"]
        tier_of: dict = {}
        bound: dict = {}
        pending: list = []
        mark = late = 0

        def by_tier(pods):
            counts = [0] * (high + 1)
            for p in pods:
                counts[tier_of[p]] += 1
            return counts

        plan = [(config["resident_pods"] // sz["scale"], low)] + [(n, high) for n in sz["trickle"]]
        for r, (arrivals, tier) in enumerate(plan):
            # from the fourth trickle round on, a tenth as many of the
            # oldest bound pods complete, five at the least: in the round
            # without arrivals evicted pods get slots back
            done = sorted(bound, key=lambda p: int(p[4:]))[: max(arrivals // 10, 5) if r > 3 else 0]
            for p in done:
                del bound[p]
            api.complete_later(done)
            for _ in range(arrivals):
                pod = f"pod_{len(tier_of)}"
                tier_of[pod] = tier
                pending.append(pod)
                api.submit_pod(PodEvent(pod_id=pod, priority=tier))
            want = reference_round(total - len(bound), by_tier(bound), by_tier(pending))
            pods = api.poll_pod_batch(0.2)
            check(len(pods) == arrivals, f"{name}: {len(pods)} pods arrived, {arrivals} sent")
            before = len(compiles)
            t0 = time.perf_counter()
            svc.run_round(pods)
            wall = time.perf_counter() - t0
            if r > 1:
                late += len(compiles) - before
            evicted, placed = [], []
            for kind, pod, node, _t in api.log[mark:]:
                if kind == "evict":
                    check(bound.pop(pod, None) == node, f"{name} round {r}: {pod} evicted from {node}")
                    pending.append(pod)
                    evicted.append(pod)
                elif kind == "bind":
                    bound[pod] = node
                    pending.remove(pod)
                    placed.append(pod)
            mark = len(api.log)
            got = (by_tier(placed), by_tier(evicted))
            check(got == want, f"{name} round {r}: bound, evicted by tier {got}, the greedy's {want}")
            check(api.completions_refused == 0, f"{name} round {r}: a completion was refused")
            check(svc.noop_rounds == 0, f"{name} round {r}: a NOOP round")
            ours = int(solver.last_result.objective)
            theirs = int(native.solve(solver.state.problem()).objective)
            check(ours == theirs, f"{name} round {r}: objective {ours} != native {theirs}")
            t = svc.scheduler.last_timing
            migrated = len(set(evicted) & set(placed))
            self.say(
                f"preemption round {r}: pods={arrivals} completions={len(done)} "
                f"bound={got[0]} evicted={got[1]} pending={by_tier(pending)} wall_ms={wall * 1e3:.1f} "
                f"graph_update_ms={t.graph_update_s * 1e3:.1f} solve_ms={t.solve_s * 1e3:.1f} "
                f"deltas_ms={t.deltas_s * 1e3:.1f} apply_ms={t.apply_s * 1e3:.1f} "
                f"supersteps={int(rung.last_supersteps)} objective={ours} "
                f"tasks_unpinned={t.tasks_unpinned} decode_tasks={t.decode_tasks} "
                f"migrated={migrated}"
            )
            check(migrated == 0, f"{name} round {r}: {migrated} pods moved for nothing")
        check(svc.ladder.degradations_total == 0, f"{name}: a step down the ladder")
        check(late == 0, f"{name}: {late} programs compiled after the first trickle round")
        faults, facts = check_priority_preemption(
            api.log, tier_of, capacity, num_nodes=args.num_machines
        )
        check(not faults, f"{name}: {faults}")
        check(facts["evicted_then_bound_again"] > 0, f"{name}: no evicted pod was bound again")
        api.close()
        st = solver.state
        return (
            f"machines={args.num_machines} nodes={st.n_cap} arcs={st.m_cap} "
            f"entries={st.plan.entry_cap} rounds={len(plan)} objectives==native and "
            f"bound / evicted by tier == the greedy's in every round; {facts['replayed']} entries "
            f"replayed: bound {facts['bound_by_tier']}, evicted {facts['evicted_by_tier']}, "
            f"{facts['evicted_then_bound_again']} evicted pods bound again, most evictions a "
            f"round {facts['most_evictions_a_round']}; compiles after the first trickle round: {late}"
        )


PHASES = (
    "served", "array", "kernels", "general", "sharded", "resident", "antiaffinity", "zonespread",
    "preemption", "quincy",
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="same phases, tiny sizes, Pallas under the interpreter, "
                    "on the CPU — the only way this script runs without a chip")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", action="append", choices=PHASES, metavar="PHASE",
                    help=f"run this phase alone (repeatable): {', '.join(PHASES)}")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before `import jax`

    import jax
    import jaxlib

    platform = jax.devices()[0].platform
    if args.rehearse_cpu:
        if platform != "cpu":
            sys.exit(f"chip_smoke: --rehearse-cpu wants the cpu platform, JAX reports {platform!r}")
    elif platform != "tpu":
        sys.exit(
            f"chip_smoke: no chip — jax.devices()[0].platform == {platform!r}, want 'tpu' "
            "(--rehearse-cpu rehearses the phases on the host)"
        )
    # a degradation, a NOOP round or a podgen retry warns; here they fail
    warnings.simplefilter("error", RuntimeWarning)

    smoke = Smoke(rehearse=args.rehearse_cpu, seed=args.seed)
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "absent"
    smoke.say(
        f"platform={smoke.device['platform']} device_kind={smoke.device['kind']!r} "
        f"count={smoke.device['count']} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu} sizes={'tiny (rehearsal)' if args.rehearse_cpu else 'full'} "
        f"seed={args.seed} compile_cache={smoke.cache_dir} "
        f"({'warm: walls of the run that filled it on file' if smoke.cold_walls else 'cold'})"
    )
    t0 = time.perf_counter()
    for name in args.only or PHASES:
        smoke.phase(name, getattr(smoke, name))
    if not args.only:
        smoke.save_walls()
    smoke.say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    result = {"ok": True, "device": smoke.device}
    if args.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
