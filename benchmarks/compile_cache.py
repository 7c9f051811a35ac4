"""Persistent XLA compile cache for the entry points that drive the chip.

`setup_compile_cache` is called first thing by `chip_smoke.py` and by the
`main()` of the scale, dynamic and availability sweeps; no library
module calls it.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing is changed.  Otherwise the cache goes to
``.jax_cache/`` at the repository root (gitignored): a fixed path, so a
later run of any of these entry points finds the programs compiled by
an earlier one.
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".jax_cache")


def setup_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; return it.

    Call before the first compilation: JAX opens the cache once per
    process, at the first compile that finds a directory configured."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
