"""Where compiled XLA programs persist between processes.

A d1024 train step takes the better part of a minute to compile on the
chip and every process starts cold, so the entry points (``chip_smoke.py``,
``fleet/replica_main.py``, the TPU test tier) share JAX's
persistent compilation cache.  The directory is part of the cache key, so
it must not move between runs: it is either wherever the operator put it
(``JAX_COMPILATION_CACHE_DIR``, which JAX reads by itself) or one fixed
path inside the checkout.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set no directory is configured in
    code — JAX already honours the variable — and its value is returned.
    Otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored).
    Call before the first compilation of the process.

    Either way the programs' metadata (scope names, source lines) becomes
    part of the cache's key.  JAX leaves it out by default, and a hit then
    returns the executable with the ``op_name`` s of whatever code compiled
    it first: ``observability.recompile.program_scopes`` and the profiler
    would describe a program by scopes it no longer has.  The price is that
    an edit which moves a line on a program's trace path compiles that
    program cold once."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
