"""Client datasets for the benchmark's configurations, made from a seed.

The glyph renderer below is the benchmark's own copy of the one behind
the program's SynthFEMNIST (``src/repro/data/synthetic.py``), widened
to any number of classes: with 62 classes and the same arguments it
draws the same random numbers in the same order, so it gives the same
data.  How clients are cut from it is a *partition*, one file per
scheme under ``chipbench/partitions/``, found by the name in the
configuration's ``dataset.partition``.

A dataset is cached in ``chipbench/.data/`` (git-ignored) as the real
rows only, keyed by its parameters, and padded on load into the dense
``[K, max_local, *row]`` layout the program takes.  A row and its label
may have any trailing shape and dtype: a ``[28, 28]`` float32 image and
one int32 class, or ``[T]`` int32 tokens and ``[T]`` int32 next-token
labels (``-1`` where a position has no target).
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import os
from dataclasses import dataclass
from pathlib import Path
from typing import Dict

import numpy as np

HERE = Path(__file__).resolve().parent
CACHE = HERE / ".data"
IMAGE_SHAPE = (28, 28)


@dataclass
class ClientData:
    """Dense padded client shards (the program's ``FederatedDataset`` layout):
    client ``k``'s first ``counts[k]`` rows are real, the rest zeros.  The
    field names are the program's: an ``images`` row may have any shape."""

    images: np.ndarray        # [K, max_n, *row], e.g. [..., 28, 28] f32
    labels: np.ndarray        # [K, max_n, *label], e.g. [K, max_n] i32
    counts: np.ndarray        # [K] i32
    test_images: np.ndarray   # [K, max_t, *row]
    test_labels: np.ndarray   # [K, max_t, *label]
    test_counts: np.ndarray   # [K] i32
    num_classes: int          # label values lie in [0, num_classes)

    @property
    def num_clients(self) -> int:
        return int(self.counts.shape[0])


def class_templates(rng: np.random.Generator, num_classes: int) -> np.ndarray:
    """``[num_classes, 28, 28]`` stroke glyphs, one per class."""
    temps = np.zeros((num_classes, *IMAGE_SHAPE), np.float32)
    yy, xx = np.mgrid[0:28, 0:28].astype(np.float32)
    for c in range(num_classes):
        n_strokes = rng.integers(2, 5)
        img = np.zeros(IMAGE_SHAPE, np.float32)
        for _ in range(n_strokes):
            pts = rng.uniform(4, 24, size=(3, 2)).astype(np.float32)
            ts = np.linspace(0, 1, 24, dtype=np.float32)[:, None]
            curve = ((1 - ts) ** 2 * pts[0] + 2 * ts * (1 - ts) * pts[1]
                     + ts**2 * pts[2])
            for cy, cx in curve:
                img += np.exp(-(((yy - cy) ** 2 + (xx - cx) ** 2) / 2.5))
        temps[c] = np.clip(img / max(img.max(), 1e-6), 0, 1)
    return temps


def writer_style(rng: np.random.Generator) -> Dict[str, float]:
    return {
        "angle": float(rng.uniform(-0.35, 0.35)),
        "scale": float(rng.uniform(0.85, 1.15)),
        "shift_y": float(rng.uniform(-2.0, 2.0)),
        "shift_x": float(rng.uniform(-2.0, 2.0)),
        "thickness": float(rng.uniform(0.7, 1.4)),
        "contrast": float(rng.uniform(0.8, 1.2)),
    }


def render(template: np.ndarray, style: Dict[str, float],
           rng: np.random.Generator) -> np.ndarray:
    """One image: a class template under a writer's style, plus noise."""
    h, w = IMAGE_SHAPE
    cy, cx = (h - 1) / 2.0, (w - 1) / 2.0
    ang, sc = style["angle"], style["scale"]
    cos_a, sin_a = np.cos(ang) / sc, np.sin(ang) / sc
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    ys = cos_a * (yy - cy) - sin_a * (xx - cx) + cy - style["shift_y"]
    xs = sin_a * (yy - cy) + cos_a * (xx - cx) + cx - style["shift_x"]
    yi = np.clip(ys, 0, h - 1).astype(np.int32)
    xi = np.clip(xs, 0, w - 1).astype(np.int32)
    img = template[yi, xi]
    img = img ** (1.0 / style["thickness"])
    img = np.clip(img * style["contrast"], 0, 1)
    img = img + rng.normal(0, 0.08, img.shape).astype(np.float32)
    return np.clip(img, 0, 1).astype(np.float32)


def _partition(name: str):
    path = HERE / "partitions" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no partition {name!r} (looked for {path})")
    spec = importlib.util.spec_from_file_location(f"chipbench_partition_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _pad(rows: np.ndarray, labels: np.ndarray, counts: np.ndarray):
    """Real rows packed client after client -> dense padded shards, rows
    and labels each keeping their trailing shape and dtype."""
    k, width = len(counts), max(int(counts.max()), 1)
    out = np.zeros((k, width, *rows.shape[1:]), rows.dtype)
    lab = np.zeros((k, width, *labels.shape[1:]), labels.dtype)
    offs = np.concatenate([[0], np.cumsum(counts)])
    for i in range(k):
        out[i, :counts[i]] = rows[offs[i]:offs[i + 1]]
        lab[i, :counts[i]] = labels[offs[i]:offs[i + 1]]
    return out, lab


def generate(ds: dict) -> dict:
    """Packed real rows of the dataset that ``ds`` describes."""
    return _partition(ds["partition"]).make(ds)


def cache_path(ds: dict) -> Path:
    """Where the dataset ``ds`` describes is cached, keyed by its
    parameters."""
    key = hashlib.sha256(json.dumps(ds, sort_keys=True).encode()).hexdigest()
    return CACHE / f"{ds['partition']}-{key[:16]}.npz"


def load(ds: dict, cache: bool = True) -> ClientData:
    """The dataset ``ds`` describes, from the cache when it is there."""
    path = cache_path(ds)
    packed = None
    if cache and path.is_file():
        with np.load(path) as z:
            packed = {k: z[k] for k in z.files}
    if packed is None:
        packed = generate(ds)
        if cache:
            CACHE.mkdir(exist_ok=True)
            tmp = path.with_suffix(f".{os.getpid()}.tmp.npz")
            np.savez(tmp, **packed)
            os.replace(tmp, path)
    images, labels = _pad(packed["images"], packed["labels"], packed["counts"])
    t_images, t_labels = _pad(packed["test_images"], packed["test_labels"],
                              packed["test_counts"])
    return ClientData(images, labels, packed["counts"].astype(np.int32),
                      t_images, t_labels,
                      packed["test_counts"].astype(np.int32),
                      int(ds["num_classes"]))
