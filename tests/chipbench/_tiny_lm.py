"""A tiny causal language model, for the tests only: the shape of a client
model that fine-tunes a frozen base, entering the harness through the
model file's optional hooks (``chipbench/harness.py``).

* ``init_shared``: a token embedding every client holds and none trains,
  used for the input and (tied) for the output logits;
* ``init_params``: one trainable block, single-head causal attention and
  a ReLU MLP, each with a residual;
* ``row_scores``: a row's share of its target positions (label ``>= 0``)
  whose label is the argmax;
* ``make_sim``: the ``FederatedSimulation`` with the shared weights
  closed over in the program's loss and accuracy.

``make`` is a token partition in the data layout ``chipbench/data.py``
pads: every client draws ``[T + 1]``-token sequences from a Markov chain
of its own (non-IID), rows are the first ``T`` tokens and labels the next
token at each position.  A test row's first ``prompt`` labels are ``-1``
(no target).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.precision import bilinear

HIGHEST = jax.lax.Precision.HIGHEST


def _shapes(m: dict) -> Dict[str, tuple]:
    d = m["width"]
    return {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
            "w1": (d, 4 * d), "w2": (4 * d, d)}


def num_params(m: dict) -> int:
    return sum(int(np.prod(s)) for s in _shapes(m).values())


def init_params(m: dict, key: jax.Array) -> Dict[str, jax.Array]:
    shapes = _shapes(m)

    def make(key):
        names = sorted(shapes)
        keys = jax.random.split(key, len(names))
        return {n: jax.random.normal(k, shapes[n], jnp.float32)
                / np.sqrt(shapes[n][0]) for n, k in zip(names, keys)}

    return jax.jit(make)(key)


def init_shared(m: dict, key: jax.Array) -> Dict[str, jax.Array]:
    return jax.jit(lambda k: {"embed": jax.random.normal(
        k, (m["vocab"], m["width"]), jnp.float32)})(key)


def _mm(a, b, precision):
    return jnp.matmul(a, b, precision=precision)


def forward(params, tokens, precision=HIGHEST, *, shared):
    """``tokens [B, T]`` -> logits ``[B, T, vocab]``."""
    dt = params["wq"].dtype
    emb = shared["embed"].astype(dt)
    h = emb[tokens]
    q, k, v = (bilinear(_mm, h, params[w], precision)
               for w in ("wq", "wk", "wv"))
    s = bilinear(_mm, q, k.swapaxes(-1, -2), precision) / np.sqrt(q.shape[-1])
    t = tokens.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e9)
    a = bilinear(_mm, jax.nn.softmax(s, axis=-1), v, precision)
    h = h + bilinear(_mm, a, params["wo"], precision)
    u = jax.nn.relu(bilinear(_mm, h, params["w1"], precision))
    h = h + bilinear(_mm, u, params["w2"], precision)
    return bilinear(_mm, h, emb.T, precision)


def loss(params, tokens, labels, precision=HIGHEST, *, shared):
    """Mean next-token cross-entropy over the target positions."""
    logp = jax.nn.log_softmax(
        forward(params, tokens, precision, shared=shared).astype(jnp.float32))
    tgt = labels >= 0
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum(nll * tgt) / jnp.maximum(jnp.sum(tgt), 1)


def row_scores(params, tokens, labels, precision=HIGHEST, *, shared):
    """``[B]``: each row's share of target positions predicted right."""
    pred = jnp.argmax(forward(params, tokens, precision, shared=shared), -1)
    tgt = labels >= 0
    return (jnp.sum((pred == labels) & tgt, axis=-1)
            / jnp.maximum(jnp.sum(tgt, axis=-1), 1))


def forward_flops(m: dict) -> int:
    """One row's projections, attention, MLP and logits; two per
    multiply-add."""
    d, t, v = m["width"], m["seq"], m["vocab"]
    return 2 * t * (4 * d * d + 8 * d * d + 2 * t * d + d * v)


# -- the system under test ---------------------------------------------------
def program_loss(params, tokens, labels, *, shared):
    return loss(params, tokens, labels, None, shared=shared)


def program_accuracy(params, tokens, labels, mask, *, shared):
    """A masked mean of the per-row scores, as ``FederatedSimulation``
    asks of ``acc_fn``."""
    s = row_scores(params, tokens, labels, None, shared=shared)
    m = mask.astype(jnp.float32)
    return jnp.sum(s * m) / jnp.maximum(jnp.sum(m), 1.0)


def make_sim(fds, params0, shared, sim_cfg):
    from repro.federated import FederatedSimulation

    def acc(*a):          # looked up per call, so a test can replace it
        return program_accuracy(*a, shared=shared)

    return FederatedSimulation(
        fds, params0, functools.partial(program_loss, shared=shared), acc,
        sim_cfg)


# -- the token partition -----------------------------------------------------
def make(ds: dict) -> dict:
    rng = np.random.default_rng(ds["data_seed"])
    v, t, k = ds["num_classes"], ds["seq"], ds["num_clients"]
    lo, hi = ds["rows"]
    counts = rng.integers(lo, hi + 1, k).astype(np.int32)
    tests = np.full(k, ds["test_rows"], np.int32)
    out = {"images": [], "labels": [], "test_images": [], "test_labels": []}
    for i in range(k):
        chain = rng.dirichlet(np.full(v, 0.1), size=v)
        for split, n in (("", counts[i]), ("test_", tests[i])):
            seq = np.empty((n, t + 1), np.int64)
            seq[:, 0] = rng.integers(0, v, n)
            for j in range(t):
                seq[:, j + 1] = [rng.choice(v, p=chain[c]) for c in seq[:, j]]
            lab = seq[:, 1:].copy()
            if split:
                lab[:, :ds["prompt"]] = -1
            out[split + "images"].append(seq[:, :t])
            out[split + "labels"].append(lab)
    return {
        **{key: np.concatenate(val).astype(np.int32)
           for key, val in out.items()},
        "counts": counts, "test_counts": tests,
    }
