"""An argument of the `service_round` span (`pods`), reduced over the solved
rounds of the window. Parameters: `arg`, `reduce`."""


def read(spec, obs):
    from benchmarks.observe import reduce_values

    if spec["arg"] != "pods":
        raise ValueError(f"span_arg reads service_round's 'pods', not {spec['arg']!r}")
    return reduce_values([float(r.pods) for r in obs.rounds if r.solved], spec["reduce"])
