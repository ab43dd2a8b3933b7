"""How the kernels meet the backend at hand.

Two decisions live here and nowhere else:

* :func:`pallas_interpret` — whether a Pallas kernel runs interpreted.  The
  CPU backend interprets (that is how the tests run); the TPU backend lowers
  through Mosaic; any other backend is an error, never a quiet fallback.
* :func:`use_compile_cache` — where JAX keeps compiled programs between
  processes.  Entry points call it once; tests do not.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "default_cache_dir", "pallas_interpret", "use_compile_cache"]

#: JAX reads this variable itself; when it is set the program sets no other
#: cache directory.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def pallas_interpret() -> bool:
    """True on the CPU backend, False on the TPU; any other backend raises."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels run on the TPU, or interpreted on the CPU; "
        f"the default backend is {backend!r}"
    )


def default_cache_dir() -> Path:
    """The fixed in-checkout cache path: ``<checkout>/.jax_cache``."""
    return Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses that directory
    and nothing is changed here.  Otherwise the cache goes to the fixed
    :func:`default_cache_dir` — a stable path, because the directory is part
    of what makes a later run find the entries.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(default_cache_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
