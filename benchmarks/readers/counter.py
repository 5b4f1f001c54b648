"""A counter the benchmark keeps over the window (`compiles_in_window`: JAX
monitoring's backend-compile events). Parameter: `counter`."""


def read(spec, obs):
    return float(obs.counters[spec["counter"]])
