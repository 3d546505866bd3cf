"""Where the persistent XLA compilation cache lives.

One rule for every entry point (``chip_smoke.py``, ``bench.py``, ``tools/``,
the tests): if ``JAX_COMPILATION_CACHE_DIR`` is set the operator has placed
the cache and JAX reads the variable itself — nothing is set in code.
Otherwise the cache goes to a FIXED path inside the checkout: a directory
that moves between runs (temp name, pid, time) never hits.
"""

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def setup_compile_cache(default_dir=DEFAULT_CACHE_DIR):
    """Point JAX's persistent compilation cache at ``default_dir`` unless
    ``JAX_COMPILATION_CACHE_DIR`` already placed it. Returns the directory
    in effect. Call before the first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", default_dir)
    return default_dir
