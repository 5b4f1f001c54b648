"""The shapes a device-resident service can compile are a closed set.

`delta_apply_fn` and `plan_apply_fn` are called with one joint pow2
record bucket each; the buckets a mirror can meet (`record_buckets`) are
compiled where its buffers are allocated, and a delta past the largest
one goes up whole. So after the round that allocates, no served round
compiles, whatever its batch. Also here: the counters that the resident
export and the pipeline's deferred POST stamp on the RoundRecord.
"""

import jax
import numpy as np
import pytest

from ksched_tpu import cli
from ksched_tpu.cluster import SyntheticClusterAPI
from ksched_tpu.cluster.api import PodEvent
from ksched_tpu.graph.device_export import (
    FULL_UPLOAD_SHARE,
    MIN_RECORD_BUCKET,
    DeviceResidentState,
    pad_record_count,
    record_buckets,
)
from ksched_tpu.runtime.trace import RoundTracer
from ksched_tpu.utils import seed_rng

#: the event benchmarks/client.CompileWatch counts: a program compiled,
#: or loaded from the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILES = []


def _on_duration(event, duration, **_kw):
    if event == COMPILE_EVENT:
        _COMPILES.append(event)


jax.monitoring.register_event_duration_secs_listener(_on_duration)

ARGV = [
    "--fake-machines", "--num-machines", "40", "--cores-per-machine", "1",
    "--pus-per-core", "4", "--max-tasks-per-pu", "4", "--cost-model", "trivial",
    "--backend", "jax", "--pod-batch-timeout", "0.002", "--pod-chan-size", "2000",
]
RESIDENT = ARGV + ["--device-resident", "--pipeline"]
FILL = 300
#: the last five came with the re-fit below `2 * m_cap`: on 40 machines the
#: plan now swings between 2,048 and 4,096 rows (the fill and each batch of
#: 250 take it up, the back-off lets it down after 2, 4, ... rounds), and at
#: 2,048 a batch of 17 or more overflows the arena of 128 rows, so the
#: layout goes up whole; the small ones scatter, each into a bucket of its own
BATCHES = (1, 3, 17, 60, 5, 250, 1, 3, 3, 12, 3, 40)
FIELDS = ("upload_bytes", "upload_full", "plan_relocations", "post_defer_ms")


class Served:
    """A service built as cli.main builds it, driven round by round."""

    def __init__(self, argv):
        seed_rng(11)
        args = cli.build_arg_parser().parse_args(argv)
        self.api = SyntheticClusterAPI(pod_chan_size=args.pod_chan_size)
        self.svc = cli.build_service(args, self.api, tracer=RoundTracer())
        self.svc.init_topology(
            fake_machines=args.num_machines, cores_per_machine=args.cores_per_machine,
            pus_per_core=args.pus_per_core,
        )
        self.live = []  # pods in arrival order: the oldest completes first
        self.n = 0

    @property
    def resident(self) -> DeviceResidentState:
        return self.svc.scheduler.solver.resident

    def round(self, arrivals: int, completions: int = 0):
        for pod_id in self.live[:completions]:
            assert self.svc.complete_pod(pod_id)
        del self.live[:completions]
        for _ in range(arrivals):
            self.live.append(f"pod{self.n}")
            self.api.submit_pod(PodEvent(pod_id=self.live[-1]))
            self.n += 1
        self.svc.run_round(self.api.poll_pod_batch(0.01))
        self.svc.run_round([], solve=False)  # the idle sweep POSTs what a pipeline deferred
        return self.svc.tracer.records[-2]

    def bindings(self):
        return sorted(self.api.bindings().items())


def _drive(argv):
    """The fill, the delta round that takes the buffers to their largest,
    then BATCHES, each with as many completions as arrivals."""
    s = Served(argv)
    records = [s.round(FILL), s.round(max(BATCHES), 0), s.round(0, max(BATCHES))]
    mark = len(_COMPILES)
    buckets = []
    for k in BATCHES:
        records.append(s.round(k, k))
        if s.resident is not None:
            buckets.append((s.resident.last_record_bucket, s.resident.last_plan_bucket))
    return s, records, len(_COMPILES) - mark, buckets


@pytest.fixture(scope="module")
def resident():
    return _drive(RESIDENT)


@pytest.fixture(scope="module")
def synchronous():
    return _drive(ARGV)


def test_no_round_after_the_allocating_ones_compiles(resident):
    s, records, compiled, buckets = resident
    assert compiled == 0
    assert all(r.num_scheduled == k for r, k in zip(records[3:], BATCHES))
    assert all(r.solver_rung == 0 and not r.noop_round for r in records)


def test_the_shapes_compiled_at_allocation_are_the_shapes_the_rounds_used(resident):
    s, _records, _compiled, buckets = resident
    res, st = s.resident, s.svc.scheduler.solver.state
    assert tuple(res._delta_set) == record_buckets(st.m_cap)
    assert tuple(res._plan_set) == record_buckets(st.plan.entry_cap)
    # bucket 0: that round's arrays, or plan (a layout rebuild), went up whole
    used_delta = {d for d, _p in buckets if d}
    used_plan = {p for _d, p in buckets if p}
    assert used_delta <= set(res._delta_set) and used_plan <= set(res._plan_set)
    # the batches wander over several buckets: the set is not one shape
    assert len(used_delta) >= 3 and len(used_plan) >= 2


def test_the_scattered_mirror_equals_a_rebuilt_one_bit_for_bit(resident):
    s, *_ = resident
    res = s.resident
    res.parity_check()
    res.plan_parity_check()
    rebuilt = DeviceResidentState(res.state)
    problem = rebuilt.refresh()
    for name in ("d_excess", "d_src", "d_dst", "d_cap", "d_cost"):
        assert np.array_equal(np.asarray(getattr(res, name)), np.asarray(getattr(rebuilt, name)))
    for ours, theirs in zip(res._sync_plan(), problem.d_plan):
        assert np.array_equal(np.asarray(ours), np.asarray(theirs))


def test_the_resident_service_binds_pod_for_pod_as_the_synchronous_one(resident, synchronous):
    assert resident[0].bindings() == synchronous[0].bindings()
    assert len(resident[0].bindings()) == FILL + max(BATCHES) + sum(BATCHES)


@pytest.mark.parametrize("field", FIELDS)
def test_a_resident_round_stamps_the_field_and_a_synchronous_one_leaves_it_zero(
    field, resident, synchronous
):
    s, records, *_ = resident
    assert all(getattr(r, field) == 0 for r in synchronous[1])
    fill = records[0]
    # a round of BATCHES whose arrays and plan both went up as records
    i = next(i for i, (d, p) in enumerate(resident[3]) if d and p)
    small, (d, p) = records[3 + i], resident[3][i]
    if field == "upload_bytes":
        st = s.svc.scheduler.solver.state
        assert fill.upload_bytes > 4 * (st.n_cap + 4 * st.m_cap) // 2  # arrays and plan, whole
        assert small.upload_bytes == 4 * (7 * d + 14 * p)  # the records of both programs
    elif field == "upload_full":
        assert fill.upload_full == 1 and small.upload_full == 0
    elif field == "plan_relocations":
        plan = s.svc.scheduler.solver.state.plan
        assert sum(r.plan_relocations for r in records) == plan.region_relocations
    else:
        # the fill's Bindings waited for the idle sweep; the next solved
        # round's record says for how long
        assert fill.post_defer_ms == 0.0 and records[1].post_defer_ms > 0.0
        assert records[3].post_defer_ms == 0.0  # the round before it bound nothing
        assert all(r.post_defer_ms > 0.0 for r in records[4:])


@pytest.mark.parametrize("extent", [16, 64, 2048, 32768, 131072])
def test_record_buckets_are_the_pow2s_up_to_the_share_of_the_buffer(extent):
    buckets = record_buckets(extent)
    assert buckets[0] == MIN_RECORD_BUCKET
    assert buckets[-1] == max(extent // FULL_UPLOAD_SHARE, MIN_RECORD_BUCKET)
    assert all(b == 2 * a for a, b in zip(buckets, buckets[1:]))


@pytest.mark.parametrize(
    "counts, bucket", [((0,), 8), ((3, 100), 128), ((9, 2, 0, 0), 16), ((5, 5000), 8192)]
)
def test_all_record_streams_of_a_program_share_one_bucket(counts, bucket):
    assert pad_record_count(*counts) == bucket


def test_a_delta_past_the_largest_bucket_goes_up_whole_and_stays_exact():
    s = Served(RESIDENT)
    s.round(60)
    res, st = s.resident, s.svc.scheduler.solver.state
    top = max(res._delta_set)
    # touch more arc slots than the largest bucket holds
    slots = np.arange(min(st.m_cap, top + 1), dtype=np.int32)
    st._dirty_slots.update(int(x) for x in slots)
    rec = s.round(2)
    assert res.last_record_bucket == 0 and res.last_upload_kind == "full_build"
    assert rec.upload_full == 1 and rec.upload_bytes >= 16 * st.m_cap
    res.parity_check()
    assert s.round(2).upload_full == 0
