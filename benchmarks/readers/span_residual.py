"""Per solved round, the time of the `den` span that lies in none of the
`leaves`: what a round holds that has no name (self time of the spans
between `den` and the leaves, and whatever runs under no span at all).
Parameters: `den` (one name), `leaves` (names of spans that are disjoint
in time: none inside another, none inside itself), `reduce`.

The reader sees each round's spans summed by name (observe.Round), not
their nesting, so disjointness is the metric file's to get right and what
can be checked is checked: a name listed twice, `den` among its own
leaves, or a round whose leaves sum to more than `den` holds (leaves that
overlap count a stretch twice) is refused with a ValueError naming the
round. A leaf the program does not open (another rung, an older program)
counts 0. None when no solved round has `den`."""

#: a round's leaves may exceed `den` by the clock's grain, not by more
SLACK_MS = 0.05


def read(spec, obs):
    from benchmarks.observe import reduce_values

    den, leaves = spec["den"], list(spec["leaves"])
    if len(set(leaves)) != len(leaves) or den in leaves:
        raise ValueError(f"span_residual: the leaves of {den!r} repeat a name or hold {den!r}")
    values = []
    for i, r in enumerate(obs.rounds):
        whole = r.spans_ms.get(den, 0.0)
        if not r.solved or whole <= 0.0:
            continue
        named = sum(r.spans_ms.get(n, 0.0) for n in leaves)
        if named > whole + SLACK_MS:
            raise ValueError(
                f"span_residual: in round {i} the leaves sum to {named:.3f} ms of a {den!r} of "
                f"{whole:.3f} ms: leaves that overlap count a stretch twice"
            )
        values.append(max(whole - named, 0.0))
    return reduce_values(values, spec["reduce"])
