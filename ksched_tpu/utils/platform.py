"""Where the program runs and where it keeps compiled code.

Every entry point (cli.main, benchmarks/run.py, chip_smoke.py,
__graft_entry__, tools/soak.py) calls `enable_compile_cache()` before
its first trace so that processes of one checkout share one persistent
XLA cache. A measurement entry point that finds no chip fails, so a
CPU reading never goes under a device metric's name: benchmarks/run.py
and chip_smoke.py check the platform themselves. The CPU path needs
only `JAX_PLATFORMS=cpu` set before `import jax`.
"""

from __future__ import annotations

import os

#: the checkout root (…/ksched_tpu/utils/platform.py -> three levels up)
CHECKOUT_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    JAX_COMPILATION_CACHE_DIR, when set, is the operator's choice and
    JAX reads it itself — nothing is set here. Otherwise the cache is
    `<checkout>/.jax_cache`: fixed (the path is part of the cache key,
    so a temp name, pid or timestamp would never hit) and git-ignored.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_stamp() -> dict:
    """The device as JAX reports it — stamped on every record a
    measurement prints, so a reading always names what it ran on."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
