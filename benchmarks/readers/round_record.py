"""A field of runtime/trace.py's RoundRecord over the window's records that
bound at least one pod. Parameters: `field`, `reduce` (a reduction of
observe.reduce_values, or `share_positive`: the share, in %, of those
records in which the field is above 0)."""


def read(spec, obs):
    from benchmarks.observe import reduce_values

    values = [float(r[spec["field"]]) for r in obs.records if r["num_scheduled"] > 0]
    if spec["reduce"] == "share_positive":
        if not values:
            return None
        return 100.0 * sum(1 for v in values if v > 0) / len(values)
    return reduce_values(values, spec["reduce"])
