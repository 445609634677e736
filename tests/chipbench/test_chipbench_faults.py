"""A whole run of a tiny cell on the CPU, past the look for a chip: a
sound program reads ``correct``, and each fault planted in the timed
path underneath reads not correct.

Faults: a round that returns the global model unchanged; half of the
cohort left out of the aggregation, the weights renormalised over the
rest; the accuracy the program reports altered where it is produced
(one test image in ten miscounted).
(The exchange between chips does not exist on one chip.)
"""
import dataclasses
import time

import pytest

from _tiny import SEED, harness, tiny_cell, tiny_data


@pytest.fixture(scope="module")
def cell_and_data():
    cell = tiny_cell("femnist_paper_adjust")
    return cell, tiny_data(cell)


def _run(cell, data):
    return harness.execute(cell, SEED, 0.5, False, time.perf_counter(),
                           data=data)


def test_sound_run_is_correct(cell_and_data):
    res = _run(*cell_and_data)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["metrics"]["updates_per_s"]["value"] > 0


def test_unchanged_state_is_not_correct(cell_and_data, monkeypatch):
    from repro.federated import engine

    step = engine.SyncStrategy.step

    def frozen(self, state, *a, **kw):
        new, ys = step(self, state, *a, **kw)
        return dataclasses.replace(new, params=state.params), ys

    monkeypatch.setattr(engine.SyncStrategy, "step", frozen)
    res = _run(*cell_and_data)
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_the_cohort_left_out_is_not_correct(cell_and_data, monkeypatch):
    import jax.numpy as jnp

    from repro.core import adjust
    from repro.federated import engine

    weights = adjust.compute_weights

    def half(c, cfg, priority=None, mask=None):
        keep = (jnp.arange(c.shape[0]) < c.shape[0] // 2).astype(jnp.float32)
        return weights(c, cfg, priority, keep if mask is None else mask * keep)

    monkeypatch.setattr(adjust, "compute_weights", half)
    monkeypatch.setattr(engine, "compute_weights", half)
    res = _run(*cell_and_data)
    assert not res["correct"]


def test_altered_answer_is_not_correct(cell_and_data, monkeypatch):
    import jax.numpy as jnp

    from repro.models import cnn

    acc = cnn.cnn_accuracy
    def off(*a, **kw):         # one test image in ten miscounted
        v = acc(*a, **kw)
        return v + jnp.where(v >= 0.1, -0.1, 0.1)

    monkeypatch.setattr(cnn, "cnn_accuracy", off)
    res = _run(*cell_and_data)
    assert not res["correct"]
    gap = res["checks"]["eval_gap"]
    assert gap["value"] > gap["limit"]
