"""Peaks of the devices the benchmark knows, and the bytes a solver
superstep has to move, computed from shapes.

A superstep of either solver is a sweep of integer compares, adds and
selects over its state: it is bound by memory traffic, not by arithmetic,
so the roofline here is bytes over peak bytes/s. The byte counts are the
least a superstep can move if every array it must read or write crosses
the memory interface once; a kernel that keeps its state in VMEM across
supersteps can beat them, and then reports a share over 100%.
"""

from __future__ import annotations

#: keyed by jax.devices()[0].device_kind. Source: Google Cloud
#: documentation, "TPU v5e" (system architecture): 197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interconnect.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flop_per_s": 197e12,
        "int8_op_per_s": 393e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """An unknown device is an error, not a default."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks on file for device kind {device_kind!r}; add it to "
            "benchmarks/roofline.py with its source"
        )
    return PEAKS[device_kind]


def scan_csr_superstep_bytes(nodes: int, arcs: int) -> int:
    """One push-relabel superstep over the scan-CSR layout
    (solver/jax_solver.py), int32 throughout. The residual graph has two
    entries per arc. Per entry: read its residual capacity, its cost and
    the potential at its head (3 x 4 B). Per arc: write the flow (4 B).
    Per node: read and write excess and potential (4 x 4 B)."""
    entries = 2 * arcs
    return 12 * entries + 4 * arcs + 16 * nodes


def transport_superstep_bytes(rows: int, cols: int) -> int:
    """One synchronous push/relabel wave over the dense [rows, cols]
    transport tile (solver/layered.py transport_superstep,
    ops/transport_pallas.py), int32. Per cell: read scaled cost, capacity
    and flow, write flow (4 x 4 B). Per column: read capacity, sink flow
    and price, write sink flow and price (5 x 4 B). Per row: read supply
    and price, write price (3 x 4 B)."""
    return 16 * rows * cols + 20 * cols + 12 * rows


def transport_cols(machines: int) -> int:
    """The padded machine axis of the transport tile: a multiple of 128
    with room for the unscheduled column (solver/layered.pad_geometry;
    copied, not imported: the yardstick does not move with the program)."""
    return ((machines + 1 + 127) // 128) * 128
