"""repro.launch subpackage: the serving, training and dry-run entry
points."""
import os
import pathlib

# one fixed place in the checkout: the path is part of what a persistent
# cache entry is found by, so it must not move between runs
COMPILE_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Keep jax's persistent compilation cache at ``COMPILE_CACHE``,
    unless ``JAX_COMPILATION_CACHE_DIR`` places it (jax reads that
    variable itself, and then nothing is set here)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
