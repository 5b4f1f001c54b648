"""A number of the reduced device trace. Parameter `value`:
  idle_share_pct     100 x (1 - busy / traced window)
  busy_ms_per_round  device-op time in the traced window over the solved
                     rounds that ended inside it
"""


def read(spec, obs):
    t = obs.trace
    if t is None:
        return None
    if spec["value"] == "idle_share_pct":
        return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
    if spec["value"] == "busy_ms_per_round":
        return 1e3 * t["busy_s"] / t["rounds"] if t["rounds"] else None
    raise ValueError(f"device_trace has no value {spec['value']!r}")
