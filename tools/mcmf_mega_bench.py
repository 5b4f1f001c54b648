#!/usr/bin/env python
"""Microbench: the Pallas MCMF megakernel vs the scan-based general
backends (CSR / ELL) on the 10k x 1k general graph.

The number this exists to pin down: docs/ROUND5.md section 5 measured
the scan-based general-graph solve at ~60 ms (CSR and ELL tie — both
gather/scan-bound, ~6 full-entry HBM passes + 3 global scans per
superstep) and identified the VMEM-resident megakernel as the lever
(predicted >= 5x from the gather arithmetic). This tool measures all
three backends on the same instance with the same protocol as
tools/csr_tpu_bench.py: cold solves (flow zeroed, eps=1 tightened
prices — the from-scratch solve the graph path issues per round),
completion barrier via scalar fetch.

Honesty notes baked into the output record:
- the megakernel runs COMPILED unless `--interpret` asks for the Pallas
  interpreter by name; a kernel the Pallas TPU compiler refuses is
  recorded as `refused_by_compiler` with the compiler's message and is
  not timed — never handed to the interpreter quietly;
- under `--interpret` the flows are bit-identical but the wall time
  measures the interpreter, not the kernel: the record says
  `mode: interpret` and marks the device claim "unmeasured".

Importable seam: bench.py's `--config mcmf-mega` calls `run_bench`.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def _solve_fns(problem, max_supersteps, backends, interpret=False):
    """Per-backend (name -> zero-arg cold-solve callable returning
    supersteps) over prebuilt plans; plan build excluded from timing.
    Only the requested backends get their plans built/uploaded.
    interpret: run the megakernel under the Pallas interpreter (an
    explicit request, never inferred from the backend)."""
    import jax
    import jax.numpy as jnp

    n = problem.num_nodes
    src = problem.src.astype(np.int32)
    dst = problem.dst.astype(np.int32)
    cap = jnp.asarray(problem.cap.astype(np.int32))
    cost = jnp.asarray(problem.cost.astype(np.int32) * np.int32(n))
    supply = jnp.asarray(problem.excess.astype(np.int32))
    m = len(src)
    eps = jnp.asarray(np.int32(1))
    zero_flow = jnp.zeros(m, jnp.int32)
    fns = {}

    if "csr" in backends or "mega" in backends:
        from ksched_tpu.solver.jax_solver import build_csr_plan

        csr_plan = build_csr_plan(src, dst, n)

    if "csr" in backends:
        from ksched_tpu.solver.jax_solver import _solve_mcmf

        csr_dev = tuple(
            jnp.asarray(x)
            for x in (
                csr_plan.s_arc, csr_plan.s_sign, csr_plan.s_src,
                csr_plan.s_dst, csr_plan.s_segstart, csr_plan.s_isstart,
                csr_plan.inv_order, csr_plan.node_first,
                csr_plan.node_last, csr_plan.node_nonempty,
            )
        )

        def run_csr():
            out = _solve_mcmf(
                cap, cost, supply, zero_flow, eps, *csr_dev,
                alpha=8, max_supersteps=max_supersteps,
            )
            jax.block_until_ready(out)
            assert bool(out[3]), "csr solve did not converge"
            return int(out[2])

        fns["csr"] = run_csr

    if "ell" in backends:
        from ksched_tpu.solver.ell_solver import (
            _plan_args, _solve_mcmf_ell, build_ell_plan,
        )

        ell_dev = _plan_args(build_ell_plan(src, dst, n))

        def run_ell():
            out = _solve_mcmf_ell(
                cap, cost, supply, zero_flow, eps, *ell_dev,
                alpha=8, max_supersteps=max_supersteps,
            )
            jax.block_until_ready(out)
            assert bool(out[3]), "ell solve did not converge"
            return int(out[2])

        fns["ell"] = run_ell

    from ksched_tpu.ops.mcmf_pallas import mcmf_loop_pallas, mega_fits_vmem
    from ksched_tpu.solver.mega_solver import build_mega_plan

    if "mega" in backends and mega_fits_vmem(2 * m):
        mega_plan = build_mega_plan(csr_plan)
        mega_dev = tuple(
            jnp.asarray(x)
            for x in (
                mega_plan.e_arc, mega_plan.e_sign, mega_plan.e_src,
                mega_plan.e_hs, mega_plan.e_he, mega_plan.e_prow,
                mega_plan.e_pcol, mega_plan.fwd_pos,
            )
        )

        def run_mega():
            out = mcmf_loop_pallas(
                cap, cost, supply, zero_flow, eps, *mega_dev,
                R=mega_plan.R, L=mega_plan.L,
                alpha=8, max_supersteps=max_supersteps,
                interpret=interpret,
            )
            jax.block_until_ready(out)
            assert bool(out[2]), "mega solve did not converge"
            return int(out[1])

        fns["mega"] = run_mega
    return fns


def run_bench(tasks=10_000, machines=1_000, solves=8,
              max_supersteps=4096, backends=("mega", "csr", "ell"),
              interpret=False):
    """Measure ms/solve + supersteps per backend; returns the record."""
    import jax

    from ksched_tpu.ops.mcmf_pallas import mega_compiler_refusal

    import __graft_entry__ as graft

    backends = tuple(b.strip() for b in backends)
    known = ("mega", "csr", "ell")
    for b in backends:
        if b not in known:
            raise SystemExit(f"unknown backend {b!r}; choose from {known}")
    problem = graft._build_problem(num_machines=machines, tasks=tasks)
    platform = jax.devices()[0].platform
    refusal = "" if interpret or "mega" not in backends else mega_compiler_refusal()
    fns = _solve_fns(
        problem, max_supersteps,
        tuple(b for b in backends if not (b == "mega" and refusal)),
        interpret=interpret,
    )
    detail = {
        "nodes": problem.num_nodes,
        "arcs": len(problem.src),
        "entries": 2 * len(problem.src),
        "solves": solves,
        "platform": platform,
    }
    per = {}
    for name in backends:
        if name not in fns:
            # only mega can be absent: refused by the compiler, or by
            # the VMEM tiling gate
            detail[name] = (
                f"refused_by_compiler: {refusal}" if refusal
                else "refused (VMEM tiling budget)"
            )
            continue
        fn = fns[name]
        steps = fn()  # warm-up / compile, excluded from timing
        walls = []
        for _ in range(solves):
            t0 = time.perf_counter()
            steps = fn()
            walls.append((time.perf_counter() - t0) * 1e3)
        per[name] = {
            "p50_ms": round(float(np.percentile(walls, 50)), 3),
            "supersteps": steps,
        }
        if name == "mega" and interpret:
            per[name]["mode"] = "interpret"
        print(f"# {name}: {per[name]}", file=sys.stderr)
    detail.update(per)
    if "mega" in per and "csr" in per:
        ratio = per["csr"]["p50_ms"] / max(per["mega"]["p50_ms"], 1e-9)
        if platform == "tpu" and not interpret:
            detail["mega_vs_csr_speedup"] = round(ratio, 2)
        else:
            detail["mega_vs_csr_speedup"] = (
                f"{round(ratio, 2)}x under the Pallas interpreter on "
                f"{platform} — the >=5x device claim is UNMEASURED"
            )
    # headline: the first measured backend in preference order (JSON
    # null when everything was refused/excluded — never a bare NaN)
    value = next(
        (per[b]["p50_ms"] for b in ("mega", "csr", "ell") if b in per),
        None,
    )
    return {
        "metric": (
            f"p50 cold-solve latency, general-graph MCMF megakernel vs "
            f"scan backends, {tasks} tasks x {machines} machines "
            f"({problem.num_nodes} nodes, {len(problem.src)} arcs), "
            f"backend=mega/{platform}"
        ),
        "value": value,
        "unit": "ms",
        "detail": detail,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tasks", type=int, default=10_000)
    ap.add_argument("--machines", type=int, default=1_000)
    ap.add_argument("--solves", type=int, default=8)
    ap.add_argument("--max-supersteps", type=int, default=4096)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--interpret", action="store_true",
                    help="run the megakernel under the Pallas interpreter "
                    "(recorded as mode: interpret)")
    ap.add_argument(
        "--backends", default="mega,csr,ell",
        help="comma-separated subset of mega,csr,ell",
    )
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    out = run_bench(
        tasks=args.tasks, machines=args.machines, solves=args.solves,
        max_supersteps=args.max_supersteps,
        backends=tuple(args.backends.split(",")),
        interpret=args.interpret,
    )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
