"""Where JAX's persistent compilation cache lives for this repository's
entry points.

JAX keys a cached executable on, among other things, the cache directory,
so a directory that moves between runs never hits.  The entry points
(`chip_smoke.py`, `examples/train_sagips_gan.py`, `repro.launch.serve` and
the proc-runtime worker) call `enable_compile_cache` at the start of their
`main`; nothing calls it at import.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (git-ignored): this file is src/repro/launch/...
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache goes to the fixed
    in-checkout `CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
