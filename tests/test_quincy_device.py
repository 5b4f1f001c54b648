"""Quincy on the device fast path: group-mode DeviceBulkCluster +
QuincyGroupTable vs the host graph path (per-task preference arcs via
GetTaskPreferenceArcs wiring + the exact SSP oracle).

Parity contract: both sides solve the same policy (route via the class
EC at worst-case transfer cost vs direct preference arcs at local
transfer cost; escape at worst+1), so with both solvers exact the
REALIZED TOTAL COST must be equal — assignments may differ among
cost-equal optima.
"""

import numpy as np
import pytest

from ksched_tpu.costmodels.quincy import QuincyCostModel
from ksched_tpu.costmodels.quincy_device import PREF_NONE, QuincyGroupTable
from ksched_tpu.data import ReferenceDescriptor, ReferenceType
from ksched_tpu.drivers import add_job, build_cluster
from ksched_tpu.scheduler.device_bulk import DeviceBulkCluster
from ksched_tpu.utils import resource_id_from_string

MB = 1 << 20


class OneTierQuincy(QuincyCostModel):
    """The served model restated to the array twin's pricing
    (costmodels/quincy_device.py): a cost unit a megabyte, the core
    switch priced as a rack switch (so a rack arc costs what X does and
    decides nothing), a direct arc to a machine that holds MORE than
    half the input, no clamp in reach."""

    QUANTUM = MB
    XI = 1
    largest_cost = 1 << 20  # two 512 MB blocks cost 1,024: beyond the served model's clamp

    def _preferred(self, held: int, total: int) -> bool:
        return 2 * held > total


# ---------------------------------------------------------------------------
# group table semantics
# ---------------------------------------------------------------------------


def test_group_table_dedupes_signatures():
    t = QuincyGroupTable(num_groups=8, num_machines=4)
    t.blocks.register(1, 512 * MB, [0])
    t.blocks.register(2, 512 * MB, [1])
    g1 = t.group_for(0, [1])
    g1b = t.group_for(0, [1])
    g2 = t.group_for(0, [2])
    g_none = t.group_for(0, [])
    assert g1 == g1b
    assert g1 != g2
    assert g_none == 0  # the class-0 fallback group
    # group 1's preference: machine 0 at transfer cost 0 (fully local)
    assert t.pref_w[g1, 0] == 0
    assert (t.pref_w[g1, 1:] == PREF_NONE).all()
    assert t.e[g1] == 512  # worst case: 512 MB remote
    assert t.u[g1] == 513


def test_group_table_overflow_goes_to_priced_overflow_group():
    # 1 class: group 0 = no-input fallback, group 1 = overflow, group 2
    # = the one free signature slot
    t = QuincyGroupTable(num_groups=3, num_machines=4)
    t.blocks.register(1, 512 * MB, [0])
    t.blocks.register(2, 256 * MB, [1])
    g1 = t.group_for(0, [1])
    assert g1 == 2
    g2 = t.group_for(0, [2])  # table full -> class overflow group
    assert g2 == 1
    assert t.overflowed == 1
    # overflow pricing is conservative: the costliest overflowed
    # signature's worst-case transfer, never an undercharge
    assert t.e[1] == 256 and t.u[1] == 257
    assert (t.pref_w[1] == t.pref_w[0]).all()  # no preferences


def test_group_table_lru_eviction_reclaims_and_reuses():
    """evict_idle reclaims zero-live groups LRU-first; freed gids are
    reused BEFORE overflowing, and an overflowed signature can register
    properly after eviction frees room."""
    t = QuincyGroupTable(num_groups=4, num_machines=4)
    # 1 class -> gids 0 (fallback), 1 (overflow), 2..3 dynamic
    t.blocks.register(1, 512 * MB, [0])
    t.blocks.register(2, 256 * MB, [1])
    t.blocks.register(3, 128 * MB, [2])
    g1 = t.group_for(0, [1])
    g2 = t.group_for(0, [2])
    assert {g1, g2} == {2, 3}
    g3 = t.group_for(0, [3])  # full -> overflow
    assert g3 == 1 and t.overflowed == 1

    # g1 has live tasks, g2 idle; touch g1 so g2 is also the LRU
    t.group_for(0, [1])
    live = np.zeros(4, np.int64)
    live[g1] = 5
    n = t.evict_idle(live, keep_fraction=0.0)
    assert n == 1 and t.evicted == 1
    assert (t.pref_w[g2] == PREF_NONE).all()
    # signature 3 was only memoized to the overflow gid; a NEW distinct
    # signature reuses the freed slot instead of overflowing
    t.blocks.register(4, 64 * MB, [3])
    g4 = t.group_for(0, [4])
    assert g4 == g2  # reused the evicted gid
    assert t.pref_w[g4, 3] == 0 and t.e[g4] == 64
    # signature 2 re-registers fresh after its eviction (not stale-mapped)
    live2 = np.zeros(4, np.int64)
    live2[g1] = 5
    live2[g4] = 1
    assert t.evict_idle(live2, keep_fraction=1.0) == 0  # under target
    g2b = t.group_for(0, [2])
    assert g2b == 1  # table full again -> overflow (g2's slot is taken)


def test_group_table_split_quanta_semantics():
    """sig_unit_mb coarser than cost_unit_mb: near-identical templates
    merge into one group while stored costs keep cost-unit resolution;
    a nonzero-cost no-preference template must NOT collapse onto the
    zero-cost fallback group; overflow pricing stays conservative
    across merged templates; finer sig than cost is rejected."""
    t = QuincyGroupTable(
        num_groups=6, num_machines=4, cost_unit_mb=1, sig_unit_mb=128
    )
    # two templates whose costs differ by < one sig quantum merge
    t.blocks.register(1, 512 * MB, [0])
    t.blocks.register(2, 513 * MB, [0])
    g1 = t.group_for(0, [1])
    g2 = t.group_for(0, [2])
    assert g1 == g2
    assert t.e[g1] == 512  # first registrant's cost-unit values
    # a 100 MB orphaned block (no holders above threshold): sig-worst
    # floors to 0 but the TRUE cost is 100 — must get its own group,
    # not the free fallback
    t.blocks.register(3, 100 * MB, [])
    g3 = t.group_for(0, [3])
    assert g3 != 0 and t.e[g3] == 100 and t.u[g3] == 101
    # genuinely-zero template still takes the fallback
    assert t.group_for(0, []) == 0

    with pytest.raises(ValueError):
        QuincyGroupTable(
            num_groups=4, num_machines=2, cost_unit_mb=64, sig_unit_mb=1
        )


def test_group_table_overflow_ratchet_covers_merged_templates():
    """With split quanta, templates merged into one overflow signature
    can differ by up to a sig quantum; the overflow price must ratchet
    on memoized hits too (never undercharge)."""
    t = QuincyGroupTable(
        num_groups=2, num_machines=4, cost_unit_mb=1, sig_unit_mb=128
    )
    # G=2 = fallback + overflow only: everything nonzero overflows
    t.blocks.register(1, 512 * MB, [0])
    t.blocks.register(2, 600 * MB, [0])  # same sig bucket (512//128 == 600//128)
    g1 = t.group_for(0, [1])
    assert g1 == 1 and t.e[1] == 512
    g2 = t.group_for(0, [2])  # memoized-sig hit on the overflow gid
    assert g2 == 1
    assert t.e[1] == 600 and t.u[1] == 601  # ratcheted to the dearer worst


def test_group_table_overflow_unpins_after_eviction():
    """A signature that first appeared under table pressure (memoized
    to the overflow gid) must register PROPERLY once eviction frees
    room — overflow pinning is pressure-scoped, not permanent."""
    t = QuincyGroupTable(num_groups=4, num_machines=4)
    t.blocks.register(1, 512 * MB, [0])
    t.blocks.register(2, 256 * MB, [1])
    t.blocks.register(3, 128 * MB, [2])
    g1 = t.group_for(0, [1])
    g2 = t.group_for(0, [2])
    g3 = t.group_for(0, [3])  # table full -> overflow, sig pinned
    assert g3 == 1
    # overflow price ratcheted to the overflowed signature's worst
    assert t.e[1] == 128
    live = np.zeros(4, np.int64)
    live[g1] = 2  # g2 idle -> evictable; overflow row idle too
    assert t.evict_idle(live, keep_fraction=0.0) == 1
    # idle overflow row's conservative ratchet reset
    assert t.e[1] == 0 and t.u[1] == 1
    g3b = t.group_for(0, [3])
    assert g3b == g2  # re-registered properly in the freed slot
    assert t.pref_w[g3b, 2] == 0 and t.e[g3b] == 128


def test_group_table_drop_machine_prunes_prefs():
    t = QuincyGroupTable(num_groups=8, num_machines=4)
    t.blocks.register(1, 512 * MB, [2])
    g = t.group_for(0, [1])
    assert t.pref_w[g, 2] == 0
    t.drop_machine(2)
    assert t.pref_w[g, 2] == PREF_NONE


def test_group_table_wait_aging():
    t = QuincyGroupTable(num_groups=4, num_machines=2)
    t.blocks.register(1, 256 * MB, [0])
    g = t.group_for(0, [1])
    u0 = t.effective_u()[g]
    t.bump_wait(np.eye(1, 4, g, dtype=np.int64)[0])
    assert t.effective_u()[g] == u0 + t.wait_cost_per_round
    t.bump_wait(np.zeros(4, np.int64))  # backlog cleared -> reset
    assert t.effective_u()[g] == u0


# ---------------------------------------------------------------------------
# parity with the host graph path
# ---------------------------------------------------------------------------


def _host_quincy_realized_cost(num_machines, slots_per_machine, task_blocks,
                               block_locs, block_size):
    """Drive the host graph path (FlowScheduler + QuincyCostModel +
    exact oracle) and return (realized_total_cost, num_placed).
    task_blocks: list of block-id lists (one per task); block_locs:
    block id -> machine indices."""
    sched, rmap, jmap, tmap, root = build_cluster(
        num_machines=num_machines,
        num_cores=1,
        pus_per_core=slots_per_machine,
        max_tasks_per_pu=1,
        cost_model_factory=OneTierQuincy,
    )
    model: OneTierQuincy = sched.cost_model
    machines = list(model._machines.keys())  # resource ids, machine order
    for b, locs in block_locs.items():
        model.blocks.register(b, block_size, [machines[m] for m in locs])
    job = add_job(sched, jmap, tmap, num_tasks=len(task_blocks))
    task_ids = [t for t, td in tmap.items() if td.job_id == str(job)]
    for tid, blocks in zip(task_ids, task_blocks):
        td = tmap.find(tid)
        for b in blocks:
            td.dependencies.append(
                ReferenceDescriptor(
                    id=b, type=ReferenceType.CONCRETE, size=block_size
                )
            )
    n, _ = sched.schedule_all_jobs()

    # realized cost: placed -> cheapest available route to the bound
    # machine (pref arc if wired there, else the EC route at worst);
    # unplaced -> escape cost
    from ksched_tpu.costmodels import CLUSTER_AGGREGATOR_EC

    bindings = sched.get_task_bindings()
    total_cost = 0
    for tid in task_ids:
        worst = model.task_to_equiv_class_aggregator(tid, CLUSTER_AGGREGATOR_EC)
        pu_rid = bindings.get(tid)
        if pu_rid is None:
            total_cost += worst + 1  # task_to_unscheduled_agg_cost, wait=0
            continue
        node = rmap.find(pu_rid).topology_node
        while node.resource_desc.type.name != "MACHINE":
            node = rmap.find(
                resource_id_from_string(node.parent_id)
            ).topology_node
        m_rid = resource_id_from_string(node.resource_desc.uuid)
        prefs = set(model.preferred_machines(tid))  # full or not: the round is over
        direct = model.task_to_resource_node_cost(tid, m_rid)
        total_cost += min(worst, direct) if m_rid in prefs else worst
    return total_cost, n


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_quincy_device_objective_matches_graph_path(seed):
    rng = np.random.default_rng(seed)
    M, S = 4, 2  # 4 machines x (1 core x 2 PUs x 1 slot) = 8 slots
    B = 5
    block_size = 512 * MB
    block_locs = {b: sorted(
        rng.choice(M, size=int(rng.integers(1, 3)), replace=False).tolist()
    ) for b in range(1, B + 1)}
    n_tasks = 10  # 10 tasks onto 8 slots -> 2 stay unscheduled
    task_blocks = []
    for _ in range(n_tasks):
        k = int(rng.integers(0, 3))
        task_blocks.append(
            sorted(rng.choice(np.arange(1, B + 1), size=k, replace=False).tolist())
        )

    host_cost, host_placed = _host_quincy_realized_cost(
        M, S, task_blocks, block_locs, block_size
    )

    table = QuincyGroupTable(num_groups=32, num_machines=M)
    for b, locs in block_locs.items():
        table.blocks.register(b, block_size, locs)
    groups = table.groups_for(
        np.zeros(n_tasks, np.int32), task_blocks
    )
    dev = DeviceBulkCluster(
        num_machines=M, pus_per_machine=S, slots_per_pu=1, num_jobs=1,
        num_task_classes=1, task_capacity=32, num_groups=32,
    )
    table.sync(dev)
    dev.add_tasks(n_tasks, groups=groups)
    stats = dev.fetch_stats(dev.round())
    assert bool(stats["converged"])
    assert int(stats["placed"]) == host_placed
    assert int(stats["objective"]) == host_cost, (
        f"device objective {int(stats['objective'])} != host graph path "
        f"{host_cost}"
    )


def test_quincy_device_bounded_window_matches_full():
    """The windowed decode must agree with the full-width decode when
    the window covers the whole backlog (group mode)."""
    M = 3
    table = QuincyGroupTable(num_groups=16, num_machines=M)
    table.blocks.register(1, 512 * MB, [1])
    table.blocks.register(2, 512 * MB, [2])
    task_blocks = [[1]] * 3 + [[2]] * 3 + [[]] * 2
    groups = table.groups_for(np.zeros(8, np.int32), task_blocks)

    outs = []
    for width in (None, 16):
        dev = DeviceBulkCluster(
            num_machines=M, pus_per_machine=2, slots_per_pu=2, num_jobs=1,
            num_task_classes=1, task_capacity=16, num_groups=16,
            decode_width=width,
        )
        table.sync(dev)
        dev.add_tasks(8, groups=groups)
        dev.round()  # full-width fill round
        s = dev.fetch_stats(dev.run_steady_rounds(4, 0.3, 1, seed=7))
        assert s["converged"].all()
        outs.append((s["placed"].sum(), s["objective"][-1]))
    assert outs[0] == outs[1]


def test_active_cap_ladder_matches_full_width():
    """The compaction LADDER (a sequence of active_groups_cap widths)
    must agree with the full-width solve at every rung: rounds whose
    active-row count fits the smallest width, a middle width, and only
    the full width all produce identical objectives/placements —
    compaction is exact, the ladder only changes which static width
    carries the solve."""
    M = 4
    table = QuincyGroupTable(num_groups=16, num_machines=M)
    for b in range(1, 9):
        table.blocks.register(b, 512 * MB, [b % M])
    rng = np.random.default_rng(3)

    outs = []
    for caps in (16, (2, 6), (1, 4, 12)):
        dev = DeviceBulkCluster(
            num_machines=M, pus_per_machine=2, slots_per_pu=2, num_jobs=1,
            num_task_classes=1, task_capacity=64, num_groups=16,
            active_groups_cap=caps,
        )
        assert dev.active_groups_caps == (
            (caps,) if isinstance(caps, int) else caps
        )
        r = np.random.default_rng(3)
        # escalating diversity: 1 group, then 3, then 8 — hits the
        # small rung, a middle rung, and the full-width fallback
        placed, objs = 0, []
        for n_groups in (1, 3, 8):
            blocks = [[int(r.integers(1, n_groups + 1))] for _ in range(6)]
            groups = table.groups_for(np.zeros(6, np.int32), blocks)
            table.sync(dev)  # push rows AFTER registration
            dev.add_tasks(6, groups=groups)
            s = dev.fetch_stats(dev.round())
            assert bool(s["converged"])
            placed += int(s["placed"])
            objs.append(int(s["objective"]))
            done = np.nonzero(
                np.asarray(dev.fetch_state()["live"])
            )[0]
            dev.complete_tasks(done.astype(np.int32))
        outs.append((placed, objs))
    assert outs[0] == outs[1] == outs[2], outs


def test_quincy_device_preemption_mode_with_groups():
    """Preemption + groups: shifting a preference (data re-replicated)
    migrates residents toward the preferred machine."""
    M = 2
    dev = DeviceBulkCluster(
        num_machines=M, pus_per_machine=1, slots_per_pu=2, num_jobs=1,
        num_task_classes=1, task_capacity=8, num_groups=2,
        preemption=True, continuation_discount=1,
    )
    pref = np.full((2, M), PREF_NONE, np.int64)
    dev.set_groups(cls=[0, 0], job=[0, 0], e=[100, 100], u=[500, 500],
                   pref_w=pref)
    dev.add_tasks(2, groups=np.array([1, 1], np.int32))
    s0 = dev.fetch_stats(dev.round())
    assert int(s0["placed"]) == 2
    # data for group 1 appears on machine 1: route 100 -> pref 10
    pref[1, 1] = 10
    dev.set_groups(pref_w=pref)
    s1 = dev.fetch_stats(dev.round())
    st = dev.fetch_state()
    on = st["pu"][:2]
    assert int(s1["migrated"]) >= 1 or (on // 1 == 1).all()
    # everyone ends on machine 1 (pu index 1): pref beats continuation
    assert (on == 1).all(), on


def test_quincy_steady_shape_two_stage_exact_and_fast():
    """The steady-state regression: residents hold the preferred
    machines, the backlog is ~a hundred near-identical rows whose only
    differentiation is a few capacity-limited pref cells. The one-shot
    dense solve herds on the uniform ground cells (measured 27k-43k
    supersteps at 10k x 1k on hardware under every eps schedule); the
    grouped round must take the exact two-stage decomposition instead:
    sparse pref matching + closed-form ground fill, tens of supersteps.
    Exactness is pinned against the host layered solver on the same
    instance."""
    from ksched_tpu.solver.layered import LayeredProblem, LayeredTransportSolver

    rng = np.random.default_rng(42)
    M, P, S, G = 64, 2, 2, 24
    dev = DeviceBulkCluster(
        num_machines=M, pus_per_machine=P, slots_per_pu=S, num_jobs=1,
        num_task_classes=1, task_capacity=512, num_groups=G,
        supersteps=1 << 15,
    )
    pref = np.full((G, M), PREF_NONE, np.int64)
    e = np.full(G, 512, np.int64)
    u = np.full(G, 513, np.int64)
    for g in range(G):
        pref[g, rng.choice(M, 2, replace=False)] = 0
    dev.set_groups(cls=np.zeros(G), job=np.zeros(G), e=e, u=u, pref_w=pref)
    n0 = 200  # fill ~78% of the 256 slots
    g0 = rng.integers(0, G, n0).astype(np.int32)
    dev.add_tasks(n0, groups=g0)
    s_fill = dev.fetch_stats(dev.round())
    assert bool(s_fill["converged"])
    # churn: complete 30 residents, admit 30 new
    st = dev.fetch_state()
    placed_rows = np.nonzero(st["live"] & (st["pu"] >= 0))[0]
    dev.complete_tasks(rng.choice(placed_rows, 30, replace=False))
    g_new = rng.integers(0, G, 30).astype(np.int32)
    dev.add_tasks(30, groups=g_new)

    # capture the pre-round instance for the host oracle
    st = dev.fetch_state()
    unpl = st["live"] & (st["pu"] < 0)
    supply = np.bincount(st["grp"][unpl], minlength=G).astype(np.int32)
    free = (S - st["pu_running"]).reshape(M, P).sum(axis=1)
    cost_eff = np.minimum(e[:, None], pref)  # route vs preference

    s = dev.fetch_stats(dev.round())
    assert bool(s["converged"])
    # the decomposition does the sparse matching only: a bounded eps=1
    # attempt (<=256) plus, on this blocked shape, the full-range
    # fallback (~900 here) — far from the one-shot dense solve's
    # herding ~34k. Residual pref-contention fights are the documented
    # remaining cost (docs/NOTES.md).
    assert int(s["supersteps"]) < 2000, int(s["supersteps"])

    want = LayeredTransportSolver().solve_layered(
        LayeredProblem(
            supply=supply,
            col_cap=free.astype(np.int32),
            cost_cm=cost_eff.astype(np.int32),
            unsched_cost=0,
            ec_cost=0,
            row_unsched_cost=u,
        )
    )
    assert int(s["objective"]) == want.objective, (
        int(s["objective"]), want.objective
    )
