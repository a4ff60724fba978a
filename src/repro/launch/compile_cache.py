"""JAX's persistent compilation cache, placed from outside or at one fixed path.

The entry points (`launch.train.main`, `launch.serve.main`,
`benchmarks.run.main`, `chip_smoke.py`) call `use_compile_cache()` before they
compile anything.  Nothing here runs at import, so importing the package (the
tests do) leaves JAX's configuration alone.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

#: the checkout's root
ROOT = Path(__file__).resolve().parents[3]
#: the checkout's own cache; the directory is part of every entry's key, so it
#: must not move between runs (no temp, pid- or time-derived path)
REPO_CACHE = ROOT / ".jax_cache"


def use_compile_cache() -> None:
    """Turn the persistent cache on.  Where ``JAX_COMPILATION_CACHE_DIR`` is
    set, JAX already reads it and no directory is set here; otherwise the
    cache lives at `REPO_CACHE`.  The key includes the programs' metadata, so
    an executable loaded from the cache carries its own ``op_name`` paths
    (named scopes), not those of a build that differed only there; source
    files enter the metadata relative to `ROOT`, so a checkout at another
    path still finds the entries."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(ROOT) + os.sep))
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
