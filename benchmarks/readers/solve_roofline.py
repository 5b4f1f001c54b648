"""The solve's share of its memory roofline: the bytes the supersteps of the
traced window had to move (roofline.py, from shapes) over the device's peak
bytes/s, over the device-op time of that window. Parameter `kernels` maps
the path that answered (`dense`, `csr`, ...) to a byte function."""


def read(spec, obs):
    from benchmarks import roofline

    t = obs.trace
    if obs.rehearsal or t is None or not t["busy_s"] or not t["supersteps"]:
        return None
    kernel = spec["kernels"][obs.shapes["path"]]
    if kernel == "transport":
        per_step = roofline.transport_superstep_bytes(
            int(obs.shapes["task_classes"]), roofline.transport_cols(int(obs.shapes["machines"]))
        )
    elif kernel == "scan_csr":
        per_step = roofline.scan_csr_superstep_bytes(
            int(obs.shapes["nodes"]), int(obs.shapes["arcs"])
        )
    else:
        raise ValueError(f"no byte function for kernel {kernel!r}")
    least_s = per_step * t["supersteps"] / roofline.peaks(obs.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / t["busy_s"]
