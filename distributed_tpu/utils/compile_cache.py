"""Persistent XLA compilation cache: one directory, placed from outside.

Compiling is a large part of every cold start this repo pays for — the
136M LM's train step, each serving dispatch, every tier-1 jit — and JAX's
persistent cache turns a repeat compile into a disk read. The cache key
includes the cache directory's own path, so a directory that moves never
hits; the rule here is therefore:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and this
  module sets nothing (child processes inherit the variable);
- where it is not, the cache is ``<checkout>/.jax_cache`` — one fixed,
  git-ignored path, never built from a hostname, pid, time or temp name.

:func:`enable` is the only place in the repo that updates
``jax_compilation_cache_dir``. Callers: ``chip_smoke.py``, ``benchmarks/``.
Entry thresholds stay at JAX's defaults (compiles under one second are not
cached). Tier-1 runs without it: on XLA:CPU every cache hit logs a
multi-kilobyte ``cpu_aot_loader`` machine-feature message to stderr.

Installed-version notes (jax/jaxlib 0.9.0, re-tried in PR 21): the XLA:CPU
executable serializer no longer corrupts the heap (the jaxlib-0.4.37 crash
that kept the cache off on CPU does not reproduce: tests/test_chunked_head.py
passes cold and warm, thresholds at zero included), and a cache entry
truncated by a kill is now a logged read error followed by a recompile, not
a crash — so the CPU skip, its ``DTPU_COMPILE_CACHE`` knob and the
atomic-write patch of ``jax._src.lru_cache`` are gone.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# The names the step program writes on its device operations
# (``jax.named_scope``: parameter paths and ``cast``/``loss``/``metrics``/
# ``optimizer``; docs/OBSERVABILITY.md) are metadata, and JAX takes the cache
# key after stripping metadata (``jax_compilation_cache_include_metadata_in_key``
# stays False: line numbers in the key would make every commit compile cold).
# So a directory warmed before the names existed, or under other names, would
# hand back an executable without them, and every trace would read nameless
# (checked on XLA:CPU in PR 24: 3 hits of 3, no scope in the executable).
# This constant enters every key instead; bump it when the names change.
SCOPE_SCHEME = "dtpu-device-scopes-1"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def cache_dir() -> str:
    """The directory the persistent cache lives in: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed in-checkout path."""
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn on the persistent compilation cache for this process and return
    its directory. Safe to call before or after the first compile — JAX
    consults the config per compilation."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    # A Pallas kernel's MLIR locations are serialized INTO its custom call
    # (the Mosaic body), which the cache key hashes. By default they carry
    # ten frames of the Python call stack of the trace, so the same train
    # step traced from two call sites got two keys (measured on the chip,
    # PR 21: fit's step vs Model.lower_train_step). One frame per location
    # — the kernel's own source line — keeps the key a function of the
    # program only, and keeps the kernel's name in the HLO op_name (turning
    # jax_include_full_tracebacks_in_locations off would drop it, and with
    # it the names a device trace is read by).
    jax.config.update("jax_traceback_in_locations_limit", 1)
    # The key's own seam for a caller's constant, path or no path: it holds
    # where JAX_COMPILATION_CACHE_DIR places the directory too.
    from jax._src import cache_key

    cache_key.custom_hook = lambda: SCOPE_SCHEME
    return path


__all__ = ["ENV_VAR", "SCOPE_SCHEME", "cache_dir", "enable"]
