"""A series the benchmark's own client keeps (`latency_ms`: due to Binding;
`late_ms`: due to submitted). Parameters: `series`, `reduce`."""


def read(spec, obs):
    from benchmarks.observe import reduce_values

    return reduce_values(obs.client[spec["series"]], spec["reduce"])
