"""Per round, the summed time of the named program spans; reduced over the
solved rounds of the window. Parameters: `spans` (names), `reduce`."""


def read(spec, obs):
    from benchmarks.observe import reduce_values

    names = spec["spans"]
    values = [
        sum(r.spans_ms.get(n, 0.0) for n in names)
        for r in obs.rounds
        if r.solved and any(n in r.spans_ms for n in names)
    ]
    return reduce_values(values, spec["reduce"])
