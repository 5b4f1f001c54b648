"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding is
exercised without TPU hardware; the driver separately dry-runs the
multi-chip path (see __graft_entry__.py).
"""

import os

# Tests run on the CPU whatever the machine holds: set before the first
# `import jax`, this is all the CPU path needs.
os.environ["JAX_PLATFORMS"] = "cpu"
# Solver-interior telemetry defaults OFF under the tier-1 wall: with it
# on, every solver test would compile the (larger) telemetry variant of
# its executable, and the suite's compile budget is the binding
# constraint. Telemetry behavior is exercised by tests/test_soltel.py
# (explicit per-solver caps, which ignore this default) and the
# chaos/obs smokes run with it ON outside the wall (`make obs-smoke`).
os.environ.setdefault("KSCHED_SOLTEL", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402

from ksched_tpu.utils import seed_rng  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy tests excluded from the budgeted tier-1 "
        "selection (-m 'not slow'); run them with a plain `pytest tests/`",
    )


@pytest.fixture(autouse=True)
def _seeded_rng():
    seed_rng(42)
    yield


#: Accepted tests under tests/benchmark/ that a later deployment made false,
#: each with the PR that did; a PR that adds a configuration may edit no file
#: the benchmark has (nor `tests/benchmark/conftest.py`, where PR 33 kept such
#: a mark). The tests are NOT taken out of the collection: they run and are
#: reported `xfailed`, only their `AssertionError` is expected, and `strict`
#: turns the run red once a `benchmark` PR has rewritten them, which deletes
#: this hook with that edit (PERF.md section 7, item (0)).
#:
#: PR 37's `test_benchmark_seams.py` states that every cell's pods are
#: `class_only` and that `benchmarks/pods/` holds that module alone.
#: `k8s-5000-preemption` (PR 38) brings `pods/by_role.py` and
#: `gtrace-12500-quincy` (PR 42) `pods/quincy_blocks.py`: parametrize the first
#: over `PLAN_DIGESTS`, list the three modules in the second.
#: `test_benchmark_preemption.py` and `test_benchmark_quincy.py` hold what
#: stays true of both.
#:
#: PR 38's `test_benchmark_preemption.py` pins its own cell to the LAST place
#: of `configs`, of `workloads` (and their number to 9) and of thirteen
#: metrics' lists; PR 42 appended a configuration, a cell and its name to
#: eleven of those lists, as the contract asks ("put new entries at the end"):
#: look the entries up by name there. `test_benchmark_quincy.py` holds what
#: stays true of that cell (`test_what_stays_true_of_the_cell_before_it`).
#:
#: PR 33's `test_benchmark_zonespread.py` and PR 41's
#: `test_benchmark_plan_refit.py` pin the cell lists of the two
#: `ec_chain_*` and the five `plan_*` metrics to what those PRs left; PR 42's
#: cell works the chain arcs and keeps a slot plan, and ISSUE 42 asks for
#: its name on those seven lists (its section 5 column reads them): state
#: the lists as "starts with" there.
#:
#: PR 42's `test_benchmark_quincy.py` pins its five entries to the last five
#: places of `per_layer` and its cell's metrics to the set PR 42 left. PR 44
#: appended `runnable_tasks_scanned` after them, as ISSUE 44 asks, with that
#: cell on its list (the scan walked 139,000 descriptors there, the most of
#: any cell): look the five up by name, state the set as "at least".
#:
#: PR 46 (`gtrace-12500-wharemap`, the second deployment on the dense rung)
#: appended a configuration, an eleventh cell, and its name to twenty-four
#: lists, as ISSUE 46 asks. `test_benchmark_quincy.py` pins its configuration
#: to the last place of `configs` (two tests); `test_benchmark_layer_spans.py`
#: and `test_benchmark_pass_metrics.py` pin the three dense-rung lists and the
#: seven `audit_*_ms` lists to the two `coco-50kx1k` cells;
#: `test_benchmark_runnable_scan.py` pins its list to nine cells;
#: `test_benchmark_seams.py` draws its cases once for each cell of
#: `BENCHMARK.json` and states that no configuration names `pods` (this one
#: says `class_only` by name, as ISSUE 46 fixes it; `PLAN_DIGESTS` has no
#: entry for it). `test_benchmark_wharemap.py` holds what stays true of each:
#: the lists as "what they had, then this cell", the plan's digests of the new
#: cell, `class_only` by name equal to `class_only` by default.
#:
#: PR 48 (the class ECs of `coco` and `whare` re-price the machines the census
#: gathered again, not every machine) made one sentence of
#: `test_benchmark_wharemap.py`'s traced rehearsal false: "every class EC of
#: the batch sweeps every machine; few of those arcs change"
#: (`ec_arcs_repriced % 312 == 0`, `ec_arcs_changed < ec_arcs_repriced`).
#: Restate it as "`ec_arcs_repriced` is visited ECs x `census_machines_dirty`
#: in a round that patched, and at most the arcs written change".
#: `tests/test_wharemap_rehearsal.py` holds every other assertion of that
#: case, on the same run, and that sentence round by round.
#:
#: PR 49 (`k8s-5000-requests`: CPU and memory requests, the source's one pod
#: size, so the scan-CSR rung) appended a configuration, a twelfth cell,
#: `pods/by_request.py`, four entries after PR 46's six, and its cell's name to
#: twenty lists, as ISSUE 49 asks. `test_benchmark_plan_refit.py` pins the five
#: plan lists to their cells case by case and `test_benchmark_quincy.py` to
#: "theirs, then quincy's"; `test_benchmark_runnable_scan.py` its
#: list; `test_benchmark_seams.py` draws a case for every cell and wants
#: `class_only`; `test_benchmark_quincy.py` lists three modules under
#: `benchmarks/pods/`; `test_benchmark_wharemap.py` pins each of its six entries
#: to its cell alone and to the end of `per_layer`. `test_benchmark_requests.py`
#: holds what stays true of each: the lists as "what they had, then this cell",
#: the four modules' purity and stamps, the six entries before the four new ones.
#: `test_benchmark_sparse_supersteps.py` (PR 50) holds what stays true of the
#: three pins of `test_benchmark_requests.py` that one more appended entry broke.
#:
#: PR 51 (the solve's split, `stats_children_gathered`, `gc_pause_ms`,
#: `round_unnamed_ms`) appended twelve entries after `supersteps_sparse_p50`, as
#: ISSUE 51 asks. `test_benchmark_sparse_supersteps.py` pins that entry to the
#: last place of `per_layer`, and PR 49's four and PR 46's six to "nothing but
#: `supersteps_sparse_p50` stands after them" (eleven cases in all): state each
#: as "in one run, in the order they were appended". `test_benchmark_solve_split.py`
#: holds what stays true of each, a case an entry.
#:
#: PR 53 (`coco-50kx1k-array`: the array round served, a service with no graph
#: path) appended a configuration, a thirteenth cell, ten entries and its cell's
#: name to three lists, as ISSUE 53 asks; and it gave the ten entries that had no
#: cell list and read nothing without a graph path the list of the twelve cells
#: that were there, which is how the driver wants "not in the new cell" said.
#: Twenty-eight cases pinned what that made false: `test_benchmark_requests.py`
#: its configuration to the last place and two lists to "this cell last";
#: `test_benchmark_solve_split.py`, `test_benchmark_pass_metrics.py` and
#: `test_benchmark_appended_metrics.py` an entry's list to "every cell of the
#: benchmark (but the rollout)" or to no list at all, a case an entry;
#: `test_benchmark_seams.py`, `test_benchmark_runnable_scan.py` and
#: `test_benchmark_solve_split.py` draw a case for every cell and want a plan
#: digest on file, or every all-cell metric loaded; `test_benchmark_sparse_supersteps.py`
#: the twelfth cell to the last place. `test_benchmark_array.py` holds what stays
#: true of each: every such entry still equals its file and lists accepted cells
#: only, this cell's plan is its control's, the ten silent entries load wherever
#: they loaded.
#:
#: PR 55 (`gtrace-12500-wharemap-array`: Whare-Map on the array round over machines
#: that differ) appended a configuration, a fourteenth cell, its name to the ten
#: `array_*` lists and to `bind_tail_ms`, `bindings_post_ms`, `gc_pause_ms`, and two
#: entries (`array_decode_width`, `array_machines_open`) that list both array cells,
#: as ISSUE 55 asks. `test_benchmark_array.py` states the three lists it joined as
#: "what they had, then this cell" over the first twelve cells (three cases) and that
#: no other list names its cell (one case: the two new entries do);
#: `test_benchmark_seams.py`, `test_benchmark_solve_split.py` and
#: `test_benchmark_runnable_scan.py` draw a case for every cell and want no `pods`
#: key, a plan digest on file, or the graph path's metrics loaded (four cases, none
#: of which existed before this cell). `test_benchmark_wharemap_array.py` holds what
#: stays true of each: the three lists as "what they had, PR 53's cell, then this
#: one", every list but the fifteen leaves both array cells out, this cell's plan is
#: its control's seed for seed and `class_only` by name is `class_only` by default.
_STALE = {
    "test_benchmark_seams.py::test_class_only_is_the_old_expression_and_the_same_seed_"
    "draws_the_same_plan[k8s-5000-preemption.rollout-": "PR 38 brings pods/by_role.py",
    "test_benchmark_seams.py::test_class_only_is_the_old_expression_and_the_same_seed_"
    "draws_the_same_plan[gtrace-12500-quincy.trickle-": "PR 42 brings pods/quincy_blocks.py",
    "test_benchmark_seams.py::test_class_only_stamps_each_event_as_it_is_made_and_draws_"
    "nothing_from_the_frameworks_rng": "PR 38 and PR 42 each bring a module under pods/",
    "test_benchmark_zonespread.py::test_each_metric_it_brings_is_an_entry_with_its_file_for_"
    "this_cell_alone[ec_chain_": "PR 42 appended its cell to the two ec_chain_* lists",
    "test_benchmark_plan_refit.py::test_the_entry_equals_its_file_and_lists_the_scan_csr_"
    "cells[": "PR 42 appended its cell to the five plan_* lists",
    "test_benchmark_plan_refit.py::test_a_cell_loads_the_five_by_name_if_it_is_listed_and_"
    "none_otherwise[gtrace-12500-quincy.trickle]": "PR 42's cell is on the five plan_* lists",
    "test_benchmark_preemption.py::test_the_configuration_is_the_sources_shapes":
        "PR 42 appended a configuration after it",
    "test_benchmark_preemption.py::test_the_cell_takes_one_chip_and_the_new_mix_completes_"
    "nothing": "PR 42 appended a tenth cell, and its name to eleven of the thirteen lists",
    "test_benchmark_quincy.py::test_the_cell_takes_one_chip_and_the_mix_it_shares_is_"
    "unchanged": "PR 44 appended runnable_tasks_scanned after its five entries, its cell listed",
    "test_benchmark_quincy.py::test_the_configuration_is_the_sources_shapes":
        "PR 46 appended a configuration after it",
    "test_benchmark_quincy.py::test_what_stays_true_of_the_cell_before_it":
        "PR 46 appended a configuration after the two it pins to the end",
    "test_benchmark_layer_spans.py::test_a_new_metric_loads_in_its_cells_and_is_absent_from_"
    "the_others[collapse_audit_ms-gtrace-12500-wharemap.trickle]":
        "PR 46's cell is answered by the dense rung",
    "test_benchmark_layer_spans.py::test_a_new_metric_loads_in_its_cells_and_is_absent_from_"
    "the_others[transport_ms-gtrace-12500-wharemap.trickle]":
        "PR 46's cell is answered by the dense rung",
    "test_benchmark_layer_spans.py::test_a_new_metric_loads_in_its_cells_and_is_absent_from_"
    "the_others[flow_reconstruct_ms-gtrace-12500-wharemap.trickle]":
        "PR 46's cell is answered by the dense rung",
    "test_benchmark_layer_spans.py::test_each_of_the_seven_entries_is_there_by_name_and_"
    "equals_its_file": "PR 46 appended its cell to the three dense-rung lists",
    "test_benchmark_pass_metrics.py::test_a_pass_metric_is_its_file_loads_in_its_cells_and_"
    "reads_what_it_names[audit_": "PR 46 appended its cell to the seven audit_*_ms lists",
    "test_benchmark_seams.py::test_class_only_is_the_old_expression_and_the_same_seed_"
    "draws_the_same_plan[gtrace-12500-wharemap.trickle-":
        "PR 46's configuration names pods class_only, and PLAN_DIGESTS has no entry for its cell",
    "test_benchmark_runnable_scan.py::test_the_entry_equals_its_file_and_lists_the_nine_"
    "cells": "PR 46 appended its cell to the list",
    "test_benchmark_runnable_scan.py::test_a_cell_loads_it_by_name_if_it_is_listed_and_not_"
    "otherwise[gtrace-12500-wharemap.trickle]": "PR 46's cell is on the list",
    "test_benchmark_plan_refit.py::test_a_cell_loads_the_five_by_name_if_it_is_listed_and_"
    "none_otherwise[k8s-5000-requests.trickle]": "PR 49's cell is on the five plan_* lists",
    "test_benchmark_quincy.py::test_what_stays_true_of_the_seven_lists_two_earlier_tests_pin":
        "PR 49 appended its cell to the five plan_* lists",
    "test_benchmark_runnable_scan.py::test_a_cell_loads_it_by_name_if_it_is_listed_and_not_"
    "otherwise[k8s-5000-requests.trickle]": "PR 49's cell is on the list",
    "test_benchmark_seams.py::test_class_only_is_the_old_expression_and_the_same_seed_"
    "draws_the_same_plan[k8s-5000-requests.trickle-": "PR 49 brings pods/by_request.py",
    "test_benchmark_quincy.py::test_a_pods_module_stamps_each_event_as_it_is_made_and_draws_"
    "nothing_from_the_frameworks_rng[": "PR 49 brings a fourth module under pods/",
    "test_benchmark_wharemap.py::test_each_metric_it_brings_is_an_entry_with_its_file_for_"
    "this_cell_alone[": "PR 49 appended four entries after the six, and its cell to one of them",
    "test_benchmark_requests.py::test_the_cell_takes_one_chip_and_the_mix_it_shares_is_"
    "unchanged": "PR 50 appended supersteps_sparse_p50, its cell listed",
    "test_benchmark_requests.py::test_each_metric_it_brings_is_an_entry_with_its_file_for_"
    "this_cell_alone[": "PR 50 appended an entry after the four",
    "test_benchmark_requests.py::test_what_stays_true_of_the_six_entries_before_them[":
        "PR 50 appended an entry after the four that follow the six",
    "test_benchmark_sparse_supersteps.py::test_the_entry_equals_its_file_and_lists_the_cells_"
    "of_plan_rows": "PR 51 appended twelve entries after it",
    "test_benchmark_sparse_supersteps.py::test_each_metric_pr_49_brought_is_still_its_file_"
    "for_its_cell_alone[": "PR 51 appended twelve entries after supersteps_sparse_p50",
    "test_benchmark_sparse_supersteps.py::test_the_six_entries_of_pr_46_still_stand_right_"
    "before_pr_49s_four[": "PR 51 appended twelve entries after supersteps_sparse_p50",
    "test_benchmark_requests.py::test_the_configuration_is_the_sources_shapes":
        "PR 53 appended a configuration after it",
    "test_benchmark_requests.py::test_a_list_it_joined_holds_what_it_held_and_this_cell_last["
    "bind_tail_ms]": "PR 53 appended its cell to the list",
    "test_benchmark_requests.py::test_a_list_it_joined_holds_what_it_held_and_this_cell_last["
    "bindings_post_ms]": "PR 53 appended its cell to the list",
    "test_benchmark_solve_split.py::test_the_entry_is_there_by_name_equals_its_file_and_lists_"
    "its_cells[round_unnamed_ms]": "PR 53's cell opens spans the reader's leaves do not name",
    "test_benchmark_solve_split.py::test_the_entry_is_there_by_name_equals_its_file_and_lists_"
    "its_cells[stats_children_gathered]": "PR 53's cell has no statistics pass",
    "test_benchmark_solve_split.py::test_the_lists_are_the_cells_the_issue_names_and_the_file_"
    "holds_together": "PR 53 appended a thirteenth cell that two of the lists leave out",
    "test_benchmark_solve_split.py::test_a_cell_loads_each_by_name_if_it_is_listed_and_not_"
    "otherwise[coco-50kx1k-array.trickle]": "PR 53's cell is on gc_pause_ms's list alone",
    "test_benchmark_seams.py::test_class_only_is_the_old_expression_and_the_same_seed_"
    "draws_the_same_plan[coco-50kx1k-array.trickle-":
        "PLAN_DIGESTS has no entry for PR 53's cell, whose plan is coco-50kx1k.trickle's",
    "test_benchmark_sparse_supersteps.py::test_the_requests_cell_is_still_the_twelfth_and_"
    "reads_what_it_read_and_this": "PR 53 appended a thirteenth cell",
    **{
        "test_benchmark_appended_metrics.py::test_an_appended_metric_is_its_file_loads_in_its_"
        f"cells_and_reads_what_it_names[{name}]": "PR 53 gave it the list of the twelve accepted "
        "cells: it reads nothing without a graph path"
        for name in ("apply_walk_ms", "decode_deltas_ms", "ec_refresh_ms", "graph_refresh_ms",
                     "runnable_scan_ms", "stats_ms")
    },
    **{
        "test_benchmark_pass_metrics.py::test_a_pass_metric_is_its_file_loads_in_its_cells_and_"
        f"reads_what_it_names[{name}]": "PR 53 appended a thirteenth cell that its list leaves out"
        for name in ("apply_full_walks", "apply_nodes_visited", "ec_purge_ms", "ec_purges",
                     "journal_apply_ms", "journal_changes", "journal_collect_ms",
                     "problem_snapshot_ms", "res_arcs_changed", "res_nodes_visited",
                     "task_refresh_ms")
    },
    "test_benchmark_runnable_scan.py::test_a_cell_loads_it_by_name_if_it_is_listed_and_not_"
    "otherwise[coco-50kx1k-array.trickle]": "PR 53's cell loads no runnable_scan_ms",
    "test_benchmark_array.py::test_a_list_it_was_appended_to_holds_what_it_had_then_this_cell[":
        "PR 55 appended its cell after PR 53's to the list",
    "test_benchmark_array.py::test_the_cell_loads_its_metrics_by_name_and_the_control_loads_none_"
    "of_them": "PR 55's two entries list PR 53's cell too",
    "test_benchmark_solve_split.py::test_a_cell_loads_each_by_name_if_it_is_listed_and_not_"
    "otherwise[gtrace-12500-wharemap-array.trickle]": "PR 55's cell is on gc_pause_ms's list alone",
    "test_benchmark_seams.py::test_class_only_is_the_old_expression_and_the_same_seed_"
    "draws_the_same_plan[gtrace-12500-wharemap-array.trickle-":
        "PR 55's configuration names pods class_only, as its control does, and PLAN_DIGESTS has "
        "no entry for its cell, whose plan is gtrace-12500-wharemap.trickle's",
    "test_benchmark_runnable_scan.py::test_a_cell_loads_it_by_name_if_it_is_listed_and_not_"
    "otherwise[gtrace-12500-wharemap-array.trickle]": "PR 55's cell loads no runnable_scan_ms",
    "test_benchmark_wharemap.py::test_the_traced_rehearsal_is_correct_and_every_metric_reads_"
    "a_number": "PR 48: `ec_arcs_repriced` is visited ECs x `census_machines_dirty` in a round "
                "that patched, and nearly every arc written changes",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for stale, why in _STALE.items():
            if stale in item.nodeid:
                item.add_marker(pytest.mark.xfail(
                    reason=f"an accepted pin that a later deployment made false ({why}); no "
                    "model_config PR may edit the file: the next benchmark PR does",
                    # KeyError: a table of the test's own with no row for a later cell
                    raises=(AssertionError, KeyError), strict=True,
                ))
