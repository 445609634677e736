"""Matrix products and convolutions at a stated precision.

A configuration states ``matmul_precision``: ``"highest"`` (float32
throughout) or ``"default"`` (float32 weights, products at the chip's
default: one bfloat16 pass, float32 accumulation).  The program runs
under it (:func:`program`); the reference runs at ``HIGHEST``; the
control one step below (:func:`control_of`).

``THREE_PASS`` is the TPU's ``high`` computed the same way on any
backend: each operand split into a bfloat16 head and a bfloat16 tail,
and the three largest of the four products summed in float32.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
THREE_PASS = "bf16_3x"


def program(config: dict):
    """The context the program is traced and run in."""
    p = config["matmul_precision"]
    if p == "default":
        return contextlib.nullcontext()
    return jax.default_matmul_precision(p)


def control_of(config: dict):
    """``(dtype, precision)`` of the control: three passes for float32 at
    ``highest``, bfloat16 for float32 at the default."""
    if config["matmul_precision"] == "highest":
        return jnp.float32, THREE_PASS
    return jnp.bfloat16, None


def _split(x):
    head = x.astype(jnp.bfloat16).astype(jnp.float32)
    return head, (x - head).astype(jnp.bfloat16).astype(jnp.float32)


def _sum3(f, a, b):
    (ah, at), (bh, bt) = _split(a), _split(b)
    return f(ah, bh) + (f(ah, bt) + f(at, bh))


def bilinear(op, a, b, precision):
    """``op(a, b, precision)``, with :data:`THREE_PASS` emulated."""
    if precision != THREE_PASS:
        return op(a, b, precision)
    return _three_pass(op, a, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _three_pass(op, a, b):
    return _sum3(lambda x, y: op(x, y, HIGHEST), a, b)


def _three_pass_fwd(op, a, b):
    return _three_pass(op, a, b), (a, b)


def _three_pass_bwd(op, res, ct):
    """The backward products in three passes too, as the TPU runs them."""
    a, b = res

    def grad_a(c, y):
        return jax.vjp(lambda x: op(x, y, HIGHEST), a)[1](c)[0]

    def grad_b(c, x):
        return jax.vjp(lambda y: op(x, y, HIGHEST), b)[1](c)[0]

    return _sum3(grad_a, ct, b), _sum3(grad_b, ct, a)


_three_pass.defvjp(_three_pass_fwd, _three_pass_bwd)
