"""jaxcache: where JAX's persistent compilation cache lives.

``CompiledLRU`` (coll/device.py) keeps executables for the life of one
process; a sweep compiles one per (kind, shape, dtype, op), and every
new process on the chip starts cold.  ``enable()`` is called wherever a
device world first touches JAX (tools/hostrun, tools/dvm,
testing.run_ranks, benchmarks/device_sweep, __graft_entry__) — before
the process's first compile, because JAX latches "no cache" at that
point — so those compiles are paid once per checkout, not per process.

The directory comes from outside when it can: with
``JAX_COMPILATION_CACHE_DIR`` in the environment JAX reads it itself
and nothing here names a directory.  Otherwise it is the fixed
``<checkout>/.jax_cache`` (git-ignored) — the path is part of the
cache's identity, so it never carries a temp name, a pid or a time.
"""

from __future__ import annotations

import os
from typing import Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """The directory the persistent cache uses when it is on."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> Optional[str]:
    """Turn the persistent compilation cache on for an accelerator
    backend; returns its directory, or None on the CPU backend.

    The CPU is left alone: XLA:CPU reloads its own entries with an
    error line per load about machine-feature strings (jaxlib 0.9.0),
    tier-1 would print thousands, and nothing needs CPU compiles kept.

    The collectives compile in well under JAX's default one-second
    persistence threshold, which would skip every one of them, so the
    time floor is dropped (the entry-size floor is already 0)."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir()
