"""Process set-up shared by the command-line entry points.

Both helpers are called from a script's ``__main__`` block, never at import:

* :func:`enable_compile_cache` turns on JAX's persistent compilation cache,
  so a second run of a script skips the compiles of the first;
* :func:`cpu_rehearsal_env` is the environment of a ``--banks N`` re-exec:
  N forced host devices on the CPU backend.  Simulated banks are a CPU
  rehearsal only — on a TPU host one chip is one bank, and the parent of a
  re-exec never touches JAX, so the child is free to start.
"""
from __future__ import annotations

import os
import pathlib

#: fixed in-checkout cache path (gitignored): the path is part of what a
#: later run must find again, so it never comes from a temp name
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed here; otherwise the cache goes to :data:`CACHE_DIR`.
    """
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def cpu_rehearsal_env(n_banks: int) -> dict:
    """Environment for a ``--banks N`` re-exec: the CPU backend with
    ``n_banks`` forced host devices."""
    return dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS=f"--xla_force_host_platform_device_count={n_banks}")
