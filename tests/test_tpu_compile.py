"""v5e compiles of the five flat-path Mosaic kernels at real widths.

No chip is needed: the TPU compiler compiles for a *described* v5e and
refuses what the chip would refuse — a block shape Mosaic cannot tile,
an op it cannot lower, more VMEM than a kernel may use — none of which
interpret mode (what every other kernel test runs) can see.  Shapes: the
paper cohort at the FEMNIST CNN's width, ``[37, 6,603,710]``, and the
``scale`` bench's cohort, ``[1024, 131072]``.  Each test asserts that
the compiled program holds the kernel (``tpu_custom_call``).

The topology is described only inside the module fixture below, never
at import: one process at a time may load the TPU library, and the
fixture runs only on the worker that is given this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import krum
from repro.kernels.divergence import divergence_sq
from repro.kernels.quantize import num_blocks, qagg
from repro.kernels.trimmed import trimmed_agg
from repro.kernels.weighted_agg import weighted_agg

PAPER_N = 6_603_710        # FEMNIST CNN parameters
SHAPES = [(37, PAPER_N), (1024, 131_072)]


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e; the persistent compile cache is off
    while it is in use (a TPU compile written there cannot be read back
    without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    set_log_dir = "TPU_LOG_DIR" not in os.environ
    if set_log_dir:
        os.environ["TPU_LOG_DIR"] = "disabled"   # no compiler logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if set_log_dir:
        del os.environ["TPU_LOG_DIR"]


def _cases(S, N):
    """``(kernel, [(shape, dtype), ...])`` for each flat kernel."""
    f32 = jnp.float32
    return {
        "weighted_agg": (lambda x, w: weighted_agg(x, w, interpret=False),
                         [((S, N), f32), ((S,), f32)]),
        "divergence_sq": (lambda x, g: divergence_sq(x, g, interpret=False),
                          [((S, N), f32), ((N,), f32)]),
        "qagg": (lambda q, s, w: qagg(q, s, w, interpret=False),
                 [((S, N), jnp.int8), ((S, num_blocks(N)), f32),
                  ((S,), f32)]),
        "trimmed_agg": (lambda x, w: trimmed_agg(x, w, S // 10,
                                                 interpret=False),
                        [((S, N), f32), ((S,), f32)]),
        "pairwise_sq_dists": (
            lambda x: krum.pairwise_sq_dists(x, interpret=False),
            [((S, N), f32)]),
    }


@pytest.mark.parametrize("S,N", SHAPES, ids=[f"S{s}" for s, _ in SHAPES])
@pytest.mark.parametrize("name", list(_cases(1, 1)))
def test_kernel_compiles_for_v5e(one_chip, name, S, N):
    fn, specs = _cases(S, N)[name]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
