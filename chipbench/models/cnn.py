"""The CNN of McMahan et al. (arXiv:1602.05629), which the paper uses on
FEMNIST: two 5x5 convolutions (SAME padding, ReLU, 2x2 max pooling
after each), a ReLU hidden layer and a linear output layer.

Everything here is the benchmark's own: the initial weights both sides
start from, the plain forward pass the reference trains and evaluates,
and the operation counts the metrics divide by.  ``program_fns`` is
the one place that names the system under test's model functions.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.precision import bilinear

Params = Dict[str, jax.Array]


def _shapes(m: dict) -> Dict[str, tuple]:
    c1, c2 = m["conv_channels"]
    k = m["kernel"]
    h, w = m["image"]
    flat = (h // 4) * (w // 4) * c2
    return {
        "conv1_w": (k, k, 1, c1), "conv1_b": (c1,),
        "conv2_w": (k, k, c1, c2), "conv2_b": (c2,),
        "fc_w": (flat, m["hidden"]), "fc_b": (m["hidden"],),
        "out_w": (m["hidden"], m["num_classes"]),
        "out_b": (m["num_classes"],),
    }


def num_params(m: dict) -> int:
    total = 0
    for shape in _shapes(m).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def init_params(m: dict, key: jax.Array, dtype=jnp.float32) -> Params:
    """He-normal weights and zero biases, in one jitted call on the device."""
    shapes = _shapes(m)

    def make(key):
        he = jax.nn.initializers.he_normal()
        names = sorted(shapes)
        keys = jax.random.split(key, len(names))
        return {n: (jnp.zeros(shapes[n], dtype) if n.endswith("_b")
                    else he(k, shapes[n], dtype))
                for n, k in zip(names, keys)}

    return jax.jit(make)(key)


def _conv(x, w, precision):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision)


def _dot(a, b, precision):
    return jnp.dot(a, b, precision=precision)


def forward(params: Params, images: jax.Array,
            precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """``images [B, 28, 28]`` -> logits ``[B, classes]`` in the params' dtype;
    ``precision`` as :func:`chipbench.precision.bilinear` takes it."""
    dt = params["conv1_w"].dtype
    x = images.astype(dt)[..., None]
    for i in (1, 2):
        x = bilinear(_conv, x, params[f"conv{i}_w"], precision) \
            + params[f"conv{i}_b"]
        x = jax.lax.reduce_window(jax.nn.relu(x), -jnp.inf, jax.lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1),
                                  "VALID")
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(bilinear(_dot, x, params["fc_w"], precision)
                    + params["fc_b"])
    return bilinear(_dot, x, params["out_w"], precision) + params["out_b"]


def loss(params: Params, images: jax.Array, labels: jax.Array,
         precision=jax.lax.Precision.HIGHEST) -> jax.Array:
    """Mean cross-entropy (computed in f32 from the logits)."""
    logits = forward(params, images, precision).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def forward_flops(m: dict) -> int:
    """Operations of one image's forward pass: the convolutions and the
    matrix products, two per multiply-add.  Bias, ReLU and pooling are
    left out."""
    c1, c2 = m["conv_channels"]
    k = m["kernel"]
    h, w = m["image"]
    conv1 = h * w * c1 * (k * k * 1) * 2
    conv2 = (h // 2) * (w // 2) * c2 * (k * k * c1) * 2
    fc = (h // 4) * (w // 4) * c2 * m["hidden"] * 2
    out = m["hidden"] * m["num_classes"] * 2
    return conv1 + conv2 + fc + out


def program_fns():
    """The system under test's loss and accuracy functions."""
    from repro.models.cnn import cnn_accuracy, cnn_loss

    return cnn_loss, cnn_accuracy
