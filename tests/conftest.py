"""Shared test configuration.

* registers the ``slow`` marker (multi-round simulations, subprocess mesh
  tests, per-arch sweeps); the default run excludes it via ``pytest.ini``
  ``addopts = -m "not slow"`` so tier-1 stays fast —
  run ``pytest -m ""`` (or ``-m slow``) for the full tier,
* pins jax to CPU so tests behave identically on accelerator hosts.
"""
import jax
import pytest

from repro.utils.compile_cache import use_compile_cache

jax.config.update("jax_platform_name", "cpu")

# Persistent XLA compilation cache: the fast tier is compile-dominated on
# CPU, so repeat runs (local iteration, CI re-runs) skip most of it.
use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-round / multi-arch tests excluded from the default "
        "fast tier (run with -m '' or -m slow)",
    )
