"""A RoundRecord field that a program may not have yet, over the window's
records that bound at least one pod. Parameters: `field`, `reduce`. Where
`round_record` raises on a record without the field, this returns None:
the metric is left out for a program that does not stamp it."""


def read(spec, obs):
    from benchmarks.observe import reduce_values

    field = spec["field"]
    values = [
        float(r[field]) for r in obs.records if r["num_scheduled"] > 0 and field in r
    ]
    return reduce_values(values, spec["reduce"])
