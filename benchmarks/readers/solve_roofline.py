"""The solve's share of its memory roofline: the bytes the supersteps of the
traced window had to move (roofline.py, from shapes) over the device's peak
bytes/s, over the device-op time of that window. Parameter `kernels` maps
the path that answered (`dense`, `csr`, ...) to a byte function. Nothing
to read, as for a span that is not there, where the service has no graph
path: `shapes` then lacks the path or a size the byte function takes."""

#: byte function -> the two shapes it takes
SIZES = {"transport": ("task_classes", "machines"), "scan_csr": ("nodes", "arcs")}


def read(spec, obs):
    from benchmarks import roofline

    t, shapes = obs.trace, obs.shapes
    if obs.rehearsal or t is None or not t["busy_s"] or not t["supersteps"]:
        return None
    if "path" not in shapes:  # a service with no graph path
        return None
    kernel = spec["kernels"][shapes["path"]]
    if kernel not in SIZES:
        raise ValueError(f"no byte function for kernel {kernel!r}")
    if any(k not in shapes for k in SIZES[kernel]):
        return None
    a, b = (int(shapes[k]) for k in SIZES[kernel])
    if kernel == "transport":
        per_step = roofline.transport_superstep_bytes(a, roofline.transport_cols(b))
    else:
        per_step = roofline.scan_csr_superstep_bytes(a, b)
    least_s = per_step * t["supersteps"] / roofline.peaks(obs.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / t["busy_s"]
