"""Per solved round, 100 x the summed time of the `num` spans over the `den`
span: the share of a round that lies inside named work. Parameters: `num`
(names), `den` (one name), `reduce`. None when no solved round has `den`."""


def read(spec, obs):
    from benchmarks.observe import reduce_values

    den = spec["den"]
    values = [
        100.0 * sum(r.spans_ms.get(n, 0.0) for n in spec["num"]) / r.spans_ms[den]
        for r in obs.rounds
        if r.solved and r.spans_ms.get(den, 0.0) > 0.0
    ]
    return reduce_values(values, spec["reduce"])
