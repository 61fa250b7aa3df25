"""TPU-native data plane: Pallas kernels, HBM reader, ICI replication, infeed."""

from __future__ import annotations


def on_tpu() -> bool:
    """True when the default JAX backend is a real TPU (Pallas compiles to
    Mosaic); off-TPU callers get interpret-mode kernels or jnp fallbacks."""
    import jax

    return jax.devices()[0].platform == "tpu"


def place_compile_cache() -> str:
    """Give JAX's persistent compilation cache its one fixed place and
    return it. Entry points (chip_smoke.py, benchmarks/run.py, __graft_entry__.py)
    call this before their first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set in code. Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout — never a temporary, per-pid or per-run name:
    the directory is part of the cache key, so one that moves never hits.
    JAX's thresholds stay at their defaults: a program that compiles in
    under ``jax_persistent_cache_min_compile_time_secs`` (1.0 s) is not
    written."""
    import os
    from pathlib import Path

    import jax

    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
