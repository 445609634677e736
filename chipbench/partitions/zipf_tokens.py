"""Token sequences for a language-model client, at any vocabulary size.

Every client has its own order of the vocabulary (a permutation) and
its own successor rule (another permutation).  A sequence starts with a
token drawn from a Zipf law over the client's order, rank ``r`` with
probability proportional to ``(r + 1) ** -zipf``; each next token is the
current token's successor with probability ``follow``, and otherwise a
fresh Zipf draw.  So next-token prediction is learnable (the successor
rule, the frequent tokens), clients are non-IID (their frequent tokens
and rules differ), and the number of distinct tokens a client holds
(``Ld``) grows with its rows.

Rows are ``seq`` tokens and their labels the next token at each
position.  A client holds ``rows[0]`` to ``rows[1]`` training rows, ``n``
of them with probability proportional to ``n ** -rows_tail`` (heavy
tailed, so ``Ds`` varies), and ``test_rows`` test rows of ``test_seq``
tokens whose first ``prompt`` labels are ``-1`` (no target).  Nothing
here grows with the square of the vocabulary.
"""
from __future__ import annotations

import numpy as np


def make(ds: dict) -> dict:
    rng = np.random.default_rng(ds["data_seed"])
    v, k = ds["num_classes"], ds["num_clients"]
    t, t_test = ds["seq"], ds["test_seq"]
    lo, hi = ds["rows"]
    sizes = np.arange(lo, hi + 1)
    p = sizes.astype(np.float64) ** -ds["rows_tail"]
    counts = rng.choice(sizes, size=k, p=p / p.sum()).astype(np.int32)
    tests = np.full(k, ds["test_rows"], np.int32)
    order = np.stack([rng.permutation(v) for _ in range(k)]).astype(np.int32)
    succ = np.stack([rng.permutation(v) for _ in range(k)]).astype(np.int32)
    cdf = np.cumsum(np.arange(1, v + 1, dtype=np.float64) ** -ds["zipf"])
    cdf /= cdf[-1]

    # every row of every client at once, test rows after training rows
    owner = np.concatenate([np.repeat(np.arange(k), counts),
                            np.repeat(np.arange(k), tests)])
    n = len(owner)

    def draw():
        rank = np.minimum(np.searchsorted(cdf, rng.random(n)), v - 1)
        return order[owner, rank]

    seq = np.empty((n, t + 1), np.int32)
    seq[:, 0] = draw()
    for j in range(t):
        follow = rng.random(n) < ds["follow"]
        seq[:, j + 1] = np.where(follow, succ[owner, seq[:, j]], draw())
    n_train = int(counts.sum())
    test = seq[n_train:, :t_test + 1]
    test_labels = test[:, 1:].copy()
    test_labels[:, :ds["prompt"]] = -1
    return {
        "images": seq[:n_train, :t], "labels": seq[:n_train, 1:],
        "counts": counts,
        "test_images": test[:, :t_test], "test_labels": test_labels,
        "test_counts": tests,
    }
