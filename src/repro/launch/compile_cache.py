"""Persistent XLA compile cache for the repo's entry points.

Every entry point (``chip_smoke.py``, ``benchmarks.run``,
``repro.launch.serve``, ``examples/policy_explorer.py``) calls
:func:`enable_compile_cache` at the top of its ``main()``; no library
module calls it on import.  The simulator compiles one engine per
(policy, cluster, shape), each taking about a second or more, so a
second run of the same command reads them back instead of compiling
again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is used and
no other path is set in code.  Otherwise the cache lives at a fixed
path inside the checkout (:data:`DEFAULT_DIR`, listed in
``.gitignore``): never a temporary, per-process or timestamped name,
since a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/...``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
#: Compiles at least this long are written to the cache.  JAX's default
#: (1 s) would skip most engines, which compile in about a second.
MIN_COMPILE_S = 0.1


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; return it."""
    import jax
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_S)
    return path
