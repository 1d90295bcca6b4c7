"""Process-wide runtime decisions shared by the compiler and the model stack.

* ``pallas_interpret()`` — the one decision on whether Pallas kernels run
  compiled (Mosaic) or in the interpreter: interpreted if and only if JAX's
  default backend is the CPU.  Every kernel, ``kernels.ops`` and
  ``core.backend_pallas`` take their ``interpret`` default from it.
* ``enable_compile_cache()`` — JAX's persistent compilation cache, called
  by the entry points (``chip_smoke.py``, ``launch.serve``,
  ``launch.train``, ``benchmarks.run``), never at import and never by
  tests.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout: the cache path is part of the cache key, so a
# directory that moves between runs would never hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: iff the default
    backend is the CPU.  On an accelerator they compile, and a kernel the
    compiler refuses raises."""
    return jax.default_backend() == "cpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX (it reads the
    variable itself) and no other directory is set in code.  Otherwise the
    cache lives at the fixed in-checkout ``.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
