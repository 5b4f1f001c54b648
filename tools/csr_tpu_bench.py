#!/usr/bin/env python
"""Record the general CSR push-relabel solver (solver/jax_solver.py) on
TPU hardware at the 10k x 1k graph-path shape — the number VERDICT r2
noted was missing (the graph path was only ever timed on JAX-CPU).

Protocol: the solve runs device-resident inside ONE dispatched scan of
N back-to-back solves (cold potentials each, flow zeroed — the
from-scratch solve the graph path issues per round), closed by the
scalar-fetch completion barrier, wall >= the 2 s floor bar
(docs/NOTES.md measurement discipline). Prints one JSON line.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--solves", type=int, default=64, help="solves per chunk")
    ap.add_argument("--chunks", type=int, default=3)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--max-supersteps", type=int, default=4096)
    ap.add_argument(
        "--layout", choices=("csr", "ell"), default="csr",
        help="general-solver data layout: sorted-entry CSR "
        "(jax_solver) or bucketed ELL (ell_solver)",
    )
    args = ap.parse_args()

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    from jax import lax

    import __graft_entry__ as graft

    problem = graft._build_problem()
    n = problem.num_nodes
    src = problem.src.astype(np.int32)
    dst = problem.dst.astype(np.int32)
    if args.layout == "ell":
        from ksched_tpu.solver.ell_solver import (
            _plan_args,
            _solve_mcmf_ell as _solve_mcmf,
            build_ell_plan,
        )

        plan_arrays = _plan_args(build_ell_plan(src, dst, n))
    else:
        from ksched_tpu.solver.jax_solver import _solve_mcmf, build_csr_plan

        plan = build_csr_plan(src, dst, n)
        plan_arrays = tuple(
            jnp.asarray(x)
            for x in (
                plan.s_arc, plan.s_sign, plan.s_src, plan.s_dst,
                plan.s_segstart, plan.s_isstart, plan.inv_order,
                plan.node_first, plan.node_last, plan.node_nonempty,
            )
        )
    cap = jnp.asarray(problem.cap.astype(np.int32))
    cost = jnp.asarray(problem.cost.astype(np.int32) * np.int32(n))
    supply = jnp.asarray(problem.excess.astype(np.int32))
    eps = jnp.asarray(np.int32(1))
    A = len(src)
    ms = args.max_supersteps

    def chain(num_solves, salt):
        """num_solves data-chained cold solves of the SAME instance:
        each solve's flow0 is zeroed THROUGH the previous result (flow
        * 0), so XLA cannot CSE or reorder them."""

        def body(carry, _):
            flow0, acc = carry
            flow, p, steps, converged, _ovf = _solve_mcmf(
                cap, cost, supply, flow0, eps, *plan_arrays,
                alpha=8, max_supersteps=ms,
            )
            return (flow * 0 + salt * 0, acc + steps), (steps, converged)

        (_, acc), (steps, conv) = lax.scan(
            body, (jnp.zeros(A, jnp.int32), jnp.int32(0)),
            None, length=num_solves,
        )
        return acc, steps, conv

    chain_jit = jax.jit(chain, static_argnums=(0,))
    devices = jax.devices()
    platform = devices[0].platform
    print(f"# platform={platform} nodes={n} arcs={A}", file=sys.stderr)

    # warm/compile
    out = chain_jit(2, jnp.int32(0))
    jax.block_until_ready(out)
    int(jax.device_get(out[0]))

    N = args.solves
    walls = []
    steps_all = None
    while True:
        walls = []
        for rep in range(args.chunks):
            t0 = time.perf_counter()
            acc, steps, conv = chain_jit(N, jnp.int32(rep))
            jax.block_until_ready(steps)
            int(jax.device_get(acc))  # the true completion barrier
            wall = (time.perf_counter() - t0) * 1e3
            walls.append(wall)
        steps_all = np.asarray(jax.device_get(steps))
        conv_all = np.asarray(jax.device_get(conv))
        assert conv_all.all(), "a solve did not converge"
        if platform == "cpu" or min(walls) >= 2000.0 or N >= (1 << 14):
            break
        N *= 4
        out = chain_jit(N, jnp.int32(0))  # recompile + drain
        jax.block_until_ready(out)
        int(jax.device_get(out[0]))

    per_solve = [w / N for w in walls]
    p50 = float(np.percentile(per_solve, 50))
    print(
        json.dumps(
            {
                "metric": (
                    f"p50 cold-solve latency, general CSR cost-scaling "
                    f"push-relabel, 10k tasks x 1k machines graph "
                    f"({n} nodes, {A} arcs), {N}-solve chains, "
                    f"backend={args.layout}/{platform}"
                ),
                "value": round(p50, 3),
                "unit": "ms",
                "vs_baseline": round(10.0 / p50, 3),
                "detail": {
                    "solves_per_chunk": N,
                    "chunks_wall_ms": [round(w, 1) for w in walls],
                    "supersteps_per_solve": int(steps_all[-1]),
                },
            }
        )
    )


if __name__ == "__main__":
    main()
