"""The precisions a run takes from its configuration: the program's
context, the control one step below, and the three-pass products the
control for float32 at ``highest`` computes on any backend."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import precision  # noqa: E402

HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, p):
    return jnp.dot(a, b, precision=p)


def _rel(x, want):
    return float(np.linalg.norm(np.asarray(x, np.float64) - want)
                 / np.linalg.norm(want))


@pytest.fixture(scope="module")
def operands():
    ka, kb = jax.random.split(jax.random.key(7))
    a = jax.random.normal(ka, (64, 256), jnp.float32)
    b = jax.random.normal(kb, (256, 32), jnp.float32)
    return a, b, np.asarray(a, np.float64) @ np.asarray(b, np.float64)


def test_three_passes_lie_between_one_pass_and_float32(operands):
    a, b, exact = operands
    three = _rel(precision.bilinear(_dot, a, b, precision.THREE_PASS), exact)
    one = _rel(_dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), None)
               .astype(jnp.float32), exact)
    f32 = _rel(_dot(a, b, HIGHEST), exact)
    assert f32 < three < one / 30, (f32, three, one)


def test_three_pass_gradient_is_three_pass_too(operands):
    a, b, _ = operands
    g = jax.random.normal(jax.random.key(8), (64, 32), jnp.float32)

    def loss(x, y, p):
        return jnp.sum(precision.bilinear(_dot, x, y, p) * g)

    want = np.asarray(g, np.float64) @ np.asarray(b, np.float64).T
    ga = jax.grad(loss)(a, b, precision.THREE_PASS)
    assert 0 < _rel(ga, want) < 1e-4
    ga32 = jax.grad(loss)(a, b, HIGHEST)
    assert _rel(ga32, want) < _rel(ga, want)


@pytest.mark.parametrize("stated, dtype, prec", [
    ("highest", jnp.float32, precision.THREE_PASS),
    ("default", jnp.bfloat16, None),
])
def test_control_is_one_step_below(stated, dtype, prec):
    assert precision.control_of({"matmul_precision": stated}) == (dtype, prec)


def test_program_runs_at_the_stated_precision():
    with precision.program({"matmul_precision": "highest"}):
        assert jax.config.jax_default_matmul_precision == "highest"
    with precision.program({"matmul_precision": "default"}):
        assert jax.config.jax_default_matmul_precision is None
