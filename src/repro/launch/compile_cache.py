"""JAX's persistent compilation cache, placed from outside or at one fixed path.

The entry points (`launch.train.main`, `launch.serve.main`,
`benchmarks.run.main`, `chip_smoke.py`) call `use_compile_cache()` before they
compile anything.  Nothing here runs at import, so importing the package (the
tests do) leaves JAX's configuration alone.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache; the directory is part of every entry's key, so it
#: must not move between runs (no temp, pid- or time-derived path)
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Turn the persistent cache on.  Where ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX already reads it and no directory is set here; otherwise the
    cache lives at `REPO_CACHE`."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
