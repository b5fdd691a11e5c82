"""Process-level JAX set-up shared by every entry point: the compile-cache
rule and the device description that goes into every summary.

The cache rule (one place, no exceptions): where `JAX_COMPILATION_CACHE_DIR`
is set, JAX reads it itself and no code here sets a directory; where it is
not, the cache lives at `<checkout>/.jax_cache`. A directory that moves
never hits, so no entry point picks its own.
"""

from __future__ import annotations

import os
from typing import Dict

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Apply the cache rule; returns the directory in use. Call once at an
    entry point, before the first compilation."""
    import jax

    # small programs count too: a live round dispatches a dozen sub-second
    # jits, and a warm start should pay for none of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # jax reads JAX_COMPILATION_CACHE_DIR into this option at import
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = os.path.join(REPO, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info() -> Dict:
    """The device as JAX reports it — every result names what it ran on."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}
