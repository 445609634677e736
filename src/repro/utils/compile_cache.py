"""Where JAX keeps its persistent compilation cache: one rule for every
entry point (``chip_smoke.py``, the examples, the benchmarks, the tests).

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  nothing is configured here.
* Otherwise: ``.jax_cache/`` at the root of the checkout.  The path is
  fixed — never a temp, pid or time-stamped directory — so a second run
  from the same checkout finds what the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout this package is imported from (``src/repro/utils`` → root)
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
