"""`quincy_blocks`: a pod reads a run of blocks of one file, and every block
has three replicas somewhere in the cluster.

Firmament's evaluation of Quincy on the Google trace synthesises what the
trace lacks: input sizes and block placement (Gog et al., OSDI'16, section
7). So does this module, from the configuration's `input` and the cluster
its `argv` builds (`--num-machines` nodes `fake_node_<i>`, node i in rack
i mod `--fake-racks`):

- a resident pod of the fill (`r<i>`) reads nothing: the harness fills the
  cluster in one round, and 135,000 pods with inputs would size the arc
  table and the slot plan for a peak no cluster has (the configuration's
  `assumed`); scheduler_perf's init pods carry no rule either;
- every other pod reads `n` consecutive blocks of `block_bytes` of one file:
  `n` = round(`blocks_median` * exp(`blocks_sigma` * z)), z standard normal,
  kept to 1 .. `blocks_max` (skewed: most pods read a few blocks and have
  machines they prefer, a few read dozens and have at best racks); the
  file Zipf-popular, rank k of `files` with weight k^-`zipf_s` (hot files
  make the pods of one round want the same machines); the run starts
  anywhere it fits in the file's `file_blocks` blocks;
- block b's replicas are a function of (seed, b) alone, so every pod that
  reads b finds it on the same nodes: one node anywhere, a second in
  another rack, the third on another node of the second's rack (none, if
  that rack has one node: the rehearsal's racks are that small).

Pure, as pods/class_only.py asks: what is drawn is drawn from a generator
of its own keyed by (seed, the pod's id), the replicas by a hash of (seed,
block); the framework's RNG is not touched.
"""

import functools

import numpy as np

from ksched_tpu.cluster.api import PodEvent

_MASK = (1 << 64) - 1


def _mix(*words: int) -> int:
    """splitmix64 over the words: a 64-bit hash, the same everywhere."""
    x = 0x9E3779B97F4A7C15
    for w in words:
        x = (x + (w & _MASK) + 0x9E3779B97F4A7C15) & _MASK
        x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK
        x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK
        x ^= x >> 31
    return x


@functools.lru_cache(maxsize=8)
def _file_cdf(files: int, zipf_s: float) -> np.ndarray:
    weights = np.arange(1, files + 1, dtype=np.float64) ** -zipf_s
    return np.cumsum(weights / weights.sum())


def cluster_of(config: dict):
    """(nodes, racks) of the cluster the configuration's argv builds."""
    argv = config["argv"]
    nodes = int(argv[argv.index("--num-machines") + 1])
    racks = int(argv[argv.index("--fake-racks") + 1])
    return nodes, max(1, min(racks, nodes))


def replicas(block: int, seed: int, nodes: int, racks: int):
    """The indices of the nodes that hold block `block`."""
    first = _mix(seed, block, 1) % nodes
    if racks < 2:
        return (first,)
    # another rack: one of the racks - 1 that follow the first's, cyclically
    rack = (first % racks + 1 + _mix(seed, block, 2) % (racks - 1)) % racks
    in_rack = (nodes - rack + racks - 1) // racks  # nodes rack, rack + racks, ...
    k = _mix(seed, block, 3) % in_rack
    second = rack + racks * k
    if in_rack < 2:
        return (first, second)
    third = rack + racks * ((k + 1 + _mix(seed, block, 4) % (in_rack - 1)) % in_rack)
    return (first, second, third)


def blocks_of(pod_id: str, config: dict, seed: int):
    """((block id, bytes, (node id, ...)), ...) of one pod with an input."""
    spec = config["input"]
    nodes, racks = cluster_of(config)
    rng = np.random.default_rng([seed, *pod_id.encode()])
    n = int(round(spec["blocks_median"] * np.exp(spec["blocks_sigma"] * rng.standard_normal())))
    n = max(1, min(n, int(spec["blocks_max"]), int(spec["file_blocks"])))
    rank = int(np.searchsorted(_file_cdf(int(spec["files"]), float(spec["zipf_s"])), rng.random()))
    rank = min(rank, int(spec["files"]) - 1)
    start = int(rng.integers(0, int(spec["file_blocks"]) - n + 1))
    first = rank * int(spec["file_blocks"]) + start
    size = int(spec["block_bytes"])
    return tuple(
        (b, size, tuple(f"fake_node_{i}" for i in replicas(b, seed, nodes, racks)))
        for b in range(first, first + n)
    )


def make(pod_id: str, task_class: int, config: dict, seed: int) -> PodEvent:
    if pod_id.startswith("r"):
        return PodEvent(pod_id=pod_id, task_class=task_class)
    return PodEvent(pod_id=pod_id, task_class=task_class, inputs=blocks_of(pod_id, config, seed))
