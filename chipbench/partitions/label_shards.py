"""Pathological non-IID partition (McMahan et al., arXiv:1602.05629 §3):
the training set, sorted by label, is cut into ``2 K`` shards of equal
size and every client gets two of them at random, so most clients see
two classes.  Every image has its own writer style.  Each client's test
set draws ``test_per_shard`` fresh images from each of its two shards'
labels."""
from __future__ import annotations

import numpy as np

from chipbench.data import class_templates, render, writer_style


def make(ds: dict) -> dict:
    rng = np.random.default_rng(ds["data_seed"])
    c = ds["num_classes"]
    templates = class_templates(rng, c)
    k, per_shard = ds["num_clients"], ds["shard_size"]
    n_shards = 2 * k
    total = n_shards * per_shard
    labels = np.arange(total) * c // total          # sorted, balanced
    shard_label = labels[::per_shard]
    owner = rng.permutation(n_shards).reshape(k, 2)
    out = {"images": [], "labels": [], "test_images": [], "test_labels": []}
    for i in range(k):
        for s in owner[i]:
            lab = int(shard_label[s])
            for split, n in (("", per_shard), ("test_", ds["test_per_shard"])):
                out[split + "images"].extend(
                    render(templates[lab], writer_style(rng), rng)
                    for _ in range(n))
                out[split + "labels"].extend([lab] * n)
    return {
        "images": np.stack(out["images"]).astype(np.float32),
        "labels": np.asarray(out["labels"], np.int32),
        "counts": np.full(k, 2 * per_shard, np.int32),
        "test_images": np.stack(out["test_images"]).astype(np.float32),
        "test_labels": np.asarray(out["test_labels"], np.int32),
        "test_counts": np.full(k, 2 * ds["test_per_shard"], np.int32),
    }
