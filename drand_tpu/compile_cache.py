"""Where JAX keeps its persistent compilation cache, for every entry point.

One rule, used by the daemon (`cli start`), `bench.py`, `tools/autotune.py`,
`__graft_entry__.py`, `chip_smoke.py` and the test suite: when
`JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and nothing here
touches the config; otherwise the cache lives at the fixed
`<checkout>/.jax_cache/` (listed in `.gitignore`).  The path is part of
the cache key, so it never depends on a pid, a clock or a temp name.

Either way, source locations leave the lowered programs.  A Pallas
kernel's lowered body (an opaque string in the program, so part of the
cache key) carries the Python call stack of whichever program traced the
kernel first in the process.  Threads that trace kernels in a varying
order then change every key from one run to the next, and the cache
never hits: a second chip_smoke run on the cache the first one filled
compiled every program again (PR 21, chip run 4).
"""

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable() -> str:
    """Place JAX's persistent compile cache; returns its directory."""
    import jax
    jax.config.update("jax_traceback_in_locations_limit", 0)
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
