"""A language-model client through the harness: token rows ``[n, T]``,
next-token labels, per-row scores and a frozen embedding every client
shares, entering by the model file's hooks alone (``_tiny_lm.py``,
found by name in place of ``chipbench/models/<kind>.py`` and
``chipbench/partitions/<name>.py``, as a new model's files would be).  A whole run of a
tiny cell on the CPU with the paper's recipe (Md > Ds > Ld, Algorithm-1
on) reads ``correct``; faults planted in the timed path read not
correct; the shared weights come out of the rounds bit for bit as they
went in, and the reference trains only the initial model's leaves."""
import time

import numpy as np
import pytest

import _tiny_lm
from _tiny import SEED, datasets, harness
from chipbench import reference

MODEL = {"kind": "tiny_lm", "vocab": 32, "seq": 8, "width": 16}
DATASET = {"partition": "tokens", "num_clients": 8, "num_classes": 32,
           "seq": 8, "rows": [6, 20], "test_rows": 4, "prompt": 2,
           "data_seed": 5}


def lm_cell() -> dict:
    paper = harness.find_cell("femnist_paper_adjust")
    config = {"name": "tiny_lm", "model": MODEL, "dtype": "float32",
              "matmul_precision": "highest", "dataset": DATASET,
              "sim_seed": 0}
    return dict(paper, config=config,
                workload=dict(paper["workload"], name="tiny_lm_adjust"),
                traffic=dict(paper["traffic"], fraction=0.5))


@pytest.fixture
def lm(monkeypatch):
    """The cell and its data, with the harness finding the tiny LM and
    its partition by name."""
    real_model, real_part = harness.model_module, datasets._partition
    monkeypatch.setattr(harness, "model_module", lambda c: (
        _tiny_lm if c["model"]["kind"] == "tiny_lm" else real_model(c)))
    monkeypatch.setattr(datasets, "_partition", lambda n: (
        _tiny_lm if n == "tokens" else real_part(n)))
    cell = lm_cell()
    return cell, datasets.load(DATASET, cache=False)


def _run(cell, data):
    return harness.execute(cell, SEED, 0.5, False, time.perf_counter(),
                           data=data)


def test_token_rows_keep_their_shape(lm):
    _, data = lm
    assert data.images.dtype == np.int32 and data.labels.dtype == np.int32
    width = int(data.counts.max())
    assert data.images.shape == data.labels.shape == (8, width, 8)
    assert data.test_labels.shape == (8, 4, 8)
    assert (data.test_labels[:, :, :2] == -1).all()
    assert (data.labels[0, :data.counts[0]] >= 0).all()


def test_sound_run_is_correct_and_shared_weights_unchanged(lm, monkeypatch):
    cell, data = lm
    given = []
    make_sim = _tiny_lm.make_sim

    def spy(fds, params0, shared, sim_cfg):
        given.append(shared)
        return make_sim(fds, params0, shared, sim_cfg)

    monkeypatch.setattr(_tiny_lm, "make_sim", spy)
    res = _run(cell, data)
    assert res["correct"], res["checks"]
    assert res["checks"]["window_compiles"]["value"] == 0
    # what the program held through the rounds is what the seed makes
    fresh = harness.init_shared(_tiny_lm, MODEL, SEED)
    (held,) = given
    assert set(held) == {"embed"}
    assert np.asarray(held["embed"]).tobytes() == \
        np.asarray(fresh["embed"]).tobytes()


def test_shared_weights_are_not_the_trained_ones(lm):
    params = _tiny_lm.init_params(MODEL, harness.seed_key(SEED))
    shared = harness.init_shared(_tiny_lm, MODEL, SEED)
    other = harness.init_shared(_tiny_lm, MODEL, SEED + 1)
    assert set(params).isdisjoint(shared)
    assert not np.array_equal(shared["embed"], other["embed"])


def test_reference_trains_only_the_initial_leaves(lm):
    cell, data = lm
    rec = harness.recipe(cell["config"], cell["traffic"], data.counts)
    w0 = {k: np.asarray(v) for k, v in _tiny_lm.init_params(
        MODEL, harness.seed_key(SEED)).items()}
    shared = harness.init_shared(_tiny_lm, MODEL, SEED)
    before = np.asarray(shared["embed"]).copy()
    out = reference.Reference(data, _tiny_lm, rec).run(
        w0, rec["checked_rounds"], shared=shared)
    assert set(out["params"]) == set(w0)
    for k in w0:
        assert not np.array_equal(out["params"][k], w0[k]), k
    assert np.array_equal(np.asarray(shared["embed"]), before)
    assert all(0.0 <= a <= 1.0 for a in out["acc"])


def test_half_the_cohort_left_out_is_not_correct(lm, monkeypatch):
    import jax.numpy as jnp

    from repro.core import adjust
    from repro.federated import engine

    weights = adjust.compute_weights

    def half(c, cfg, priority=None, mask=None):
        keep = (jnp.arange(c.shape[0]) < c.shape[0] // 2).astype(jnp.float32)
        return weights(c, cfg, priority, keep if mask is None else mask * keep)

    monkeypatch.setattr(adjust, "compute_weights", half)
    monkeypatch.setattr(engine, "compute_weights", half)
    res = _run(*lm)
    assert not res["correct"]


def test_altered_answer_is_not_correct(lm, monkeypatch):
    import jax.numpy as jnp

    acc = _tiny_lm.program_accuracy

    def off(*a, **kw):         # one test row in ten miscounted
        v = acc(*a, **kw)
        return v + jnp.where(v >= 0.1, -0.1, 0.1)

    monkeypatch.setattr(_tiny_lm, "program_accuracy", off)
    res = _run(*lm)
    assert not res["correct"]
    gap = res["checks"]["eval_gap"]
    assert gap["value"] > gap["limit"]
