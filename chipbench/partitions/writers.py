"""Writer partition (LEAF FEMNIST's layout): each client is one writer
with its own style, a skewed subset of the classes, and a power-law
number of samples.  With 62 classes this draws exactly what the
program's ``make_synth_femnist`` draws for the same arguments."""
from __future__ import annotations

import numpy as np

from chipbench.data import class_templates, render, writer_style


def make(ds: dict) -> dict:
    rng = np.random.default_rng(ds["data_seed"])
    templates = class_templates(rng, ds["num_classes"])
    k = ds["num_clients"]
    raw = rng.pareto(2.5, k) + 1.0
    sizes = np.maximum(8, (raw / raw.mean() * ds["mean_samples"])).astype(
        np.int64)
    test_sizes = np.maximum(2, (sizes * ds["test_fraction"]).astype(np.int64))
    out = {"images": [], "labels": [], "test_images": [], "test_labels": []}
    lo, hi = ds["classes_per_writer"]
    for i in range(k):
        style = writer_style(rng)
        n_cls = int(rng.integers(lo, hi))
        classes = rng.choice(ds["num_classes"], size=n_cls, replace=False)
        props = rng.dirichlet(np.full(n_cls, 0.5))
        for split, n in (("", int(sizes[i])), ("test_", int(test_sizes[i]))):
            ls = rng.choice(classes, size=n, p=props)
            out[split + "images"].extend(render(templates[c], style, rng)
                                         for c in ls)
            out[split + "labels"].extend(int(c) for c in ls)
    return {
        "images": np.stack(out["images"]).astype(np.float32),
        "labels": np.asarray(out["labels"], np.int32),
        "counts": sizes.astype(np.int32),
        "test_images": np.stack(out["test_images"]).astype(np.float32),
        "test_labels": np.asarray(out["test_labels"], np.int32),
        "test_counts": test_sizes.astype(np.int32),
    }
