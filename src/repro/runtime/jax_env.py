"""Program-wide jax set-up: the float64 scope and the compile cache.

Every jitted kernel of the package reaches jax through `setup`, and
every float64 region enters `x64` — one place for both decisions.

* ``x64()`` is the scope in which the back half keeps float64 (the
  energy model's parity contract with ``backend="python"``).
* ``setup()`` turns on jax's persistent compilation cache once per
  process.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already
  reads it and no directory is set here; otherwise the cache lives at
  `CACHE_DIR`, a fixed path inside the checkout (a path that moves
  never hits, since the path is part of the cache's key).
"""

from __future__ import annotations

import os
import pathlib

#: Compile-cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_SETUP_DONE = False


def setup() -> None:
    """Enable the persistent compilation cache (idempotent)."""
    global _SETUP_DONE
    if _SETUP_DONE:
        return
    _SETUP_DONE = True
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        CACHE_DIR.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # The characterization kernels compile dozens of small shape buckets;
    # cache every one of them, not only the slow ones.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def cache_dir() -> str | None:
    """The compile-cache directory jax is using (None when off)."""
    import jax

    return jax.config.jax_compilation_cache_dir


def x64():
    """Context manager: trace and run with float64 enabled."""
    import jax

    return jax.enable_x64(True)


def pallas_interpret() -> bool:
    """Pallas kernels compile with Mosaic on a TPU and are interpreted
    on every other backend (CPU tests)."""
    import jax

    return jax.default_backend() != "tpu"
