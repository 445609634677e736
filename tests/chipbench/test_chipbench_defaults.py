"""A model file's optional hooks default to what the image cells ran with
before there were hooks, and client data of any row shape goes through
``chipbench/data.py`` as image rows always did.

The image datasets are pinned by a digest of every array, and the two
configurations' datasets by their cache file names: a change to either
would move the benchmark's readings."""
import hashlib
import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

import _tiny_lm
from _tiny import ROOT, SEED, datasets, harness, tiny_cell, tiny_data
from chipbench import reference

CNN_CONFIG = harness.load_json(ROOT / "chipbench/configs/femnist_cnn_paper.json")
CNN = harness.model_module(CNN_CONFIG)
MFU = harness.metric_reader("round_mfu")
FIELDS = ("images", "labels", "counts", "test_images", "test_labels",
          "test_counts")


def digest(d) -> str:
    h = hashlib.sha256()
    for k in FIELDS:
        a = getattr(d, k)
        h.update(f"{k}{a.shape}{a.dtype}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config", ["femnist_cnn_paper", "mnist_cnn_fedavg"])
def test_train_flops_defaults_to_three_forward_passes(config):
    m = harness.load_json(ROOT / "chipbench/configs" / f"{config}.json")["model"]
    assert not hasattr(CNN, "train_flops")
    assert harness.train_flops(CNN, m) == 3 * CNN.forward_flops(m)


def test_round_flops_take_the_models_own_training_count():
    frozen = NS(forward_flops=lambda m: 10, train_flops=lambda m: 20)
    rec = dict(S=2, steps=3, batch_size=4, online_adjust=False,
               criteria=["Ds"])
    assert MFU.round_flops(frozen, {"model": {}}, rec, 7) == 20 * 24 + 10 * 7


def test_shared_weights_default_to_none():
    assert harness.init_shared(CNN, CNN_CONFIG["model"], SEED) is None


def test_sim_defaults_to_the_programs_functions():
    from repro.models.cnn import cnn_accuracy, cnn_loss

    cell = tiny_cell("mnist_fedavg_e5")
    data = tiny_data(cell)
    rec = harness.recipe(cell["config"], cell["traffic"], data.counts)
    params = CNN.init_params(cell["config"]["model"], harness.seed_key(SEED))
    sim = harness.build_sim(cell, data, params, rec)
    assert (sim.loss_fn, sim.acc_fn) == (cnn_loss, cnn_accuracy)
    assert sim.cfg.flat_params and sim.cfg.max_rounds == rec["checked_rounds"]


def test_default_row_scores_are_argmax_hits():
    cell = tiny_cell("mnist_fedavg_e5")
    data = tiny_data(cell)
    rec = harness.recipe(cell["config"], cell["traffic"], data.counts)
    params = CNN.init_params(cell["config"]["model"], harness.seed_key(SEED))
    ref = reference.Reference(data, CNN, rec)
    x, y = data.test_images[0], data.test_labels[0]
    want = np.argmax(np.asarray(CNN.forward(params, x)), axis=-1) == y
    got = np.asarray(ref.row_scores(params, x, y, None))
    assert got.shape == (len(y),) and np.array_equal(got, want)
    # the accuracy counts the real test rows only
    hits = sum(np.sum(np.argmax(np.asarray(CNN.forward(
        params, data.test_images[k, :n])), -1) == data.test_labels[k, :n])
        for k, n in enumerate(data.test_counts))
    assert ref.accuracy([params])[0] == hits / data.test_counts.sum()


# -- datasets ----------------------------------------------------------------
IMAGE_SETS = {
    "writers": ({"partition": "writers", "num_clients": 6, "mean_samples": 10,
                 "num_classes": 62, "classes_per_writer": [8, 24],
                 "test_fraction": 0.25, "data_seed": 3},
                "ed00433044cf3dc372f8bea4c6c5f846c889b5dc69c2d53f8a74134dbe070f3d"),
    "label_shards": ({"partition": "label_shards", "num_clients": 10,
                      "shard_size": 30, "test_per_shard": 4,
                      "num_classes": 10, "data_seed": 1},
                     "28e91acdbb3151ee97d28955c4c47526e93f31a5a71373d1e7c29029df5590f1"),
}


@pytest.mark.parametrize("cache", [False, True])
@pytest.mark.parametrize("name", sorted(IMAGE_SETS))
def test_image_datasets_load_byte_identical(name, cache, tmp_path,
                                            monkeypatch):
    monkeypatch.setattr(datasets, "CACHE", tmp_path)
    ds, want = IMAGE_SETS[name]
    for _ in range(2 if cache else 1):      # made, then read back
        d = datasets.load(ds, cache=cache)
        assert digest(d) == want
    assert d.labels.dtype == np.int32 and d.images.dtype == np.float32


@pytest.mark.parametrize("config,name", [
    ("femnist_cnn_paper", "writers-b8fc0efb019d96cd.npz"),
    ("mnist_cnn_fedavg", "label_shards-88affddde7a178ab.npz"),
])
def test_configured_datasets_keep_their_cache_key(config, name):
    cfg = harness.load_json(ROOT / "chipbench/configs" / f"{config}.json")
    path = datasets.cache_path(cfg["dataset"])
    assert path.name == name and path.parent == datasets.CACHE


def test_token_rows_round_trip_through_the_cache(tmp_path, monkeypatch):
    ds = {"partition": "tokens", "num_clients": 4, "num_classes": 20,
          "seq": 6, "rows": [3, 9], "test_rows": 2, "prompt": 1,
          "data_seed": 11}
    monkeypatch.setattr(datasets, "CACHE", tmp_path)
    monkeypatch.setattr(datasets, "_partition", lambda n: _tiny_lm)
    made = datasets.load(ds)
    assert datasets.cache_path(ds).is_file()
    monkeypatch.setattr(datasets, "generate", lambda ds: pytest.fail(
        "a cached dataset was made again"))
    read = datasets.load(ds)
    assert digest(read) == digest(made)
    width = int(made.counts.max())
    assert made.images.shape == made.labels.shape == (4, width, 6)
    assert made.test_labels.shape == (4, 2, 6)
    assert made.images.dtype == made.labels.dtype == np.int32
    for k in range(4):                     # padding after the real rows
        assert not made.labels[k, made.counts[k]:].any()
    assert (made.test_labels[:, :, 0] == -1).all()


# -- Algorithm-1's slack -----------------------------------------------------
@pytest.mark.parametrize("q,prev_q,cur,pick,want", [
    # the current order does not regress: keeping it is the rule
    ([0.5, 0.6, 0.4], 0.5, 1, 1, 0.0),
    # it regresses and the first other order that does not is taken
    ([0.5, 0.3, 0.55], 0.5, 1, 0, 0.0),
    # every order regresses: the best is taken, here the current one
    ([0.2, 0.3, 0.25], 0.5, 1, 1, 0.0),
    # the current order kept though another does not regress
    ([0.6, 0.3, 0.2], 0.5, 1, 1, 0.2),
    # a regressing order taken past the current one, which does not
    ([0.2, 0.55, 0.1], 0.5, 1, 2, 0.4),
])
def test_slack_of_a_pick(q, prev_q, cur, pick, want):
    got = reference.slack(np.asarray(q), prev_q, cur, pick)
    assert got == pytest.approx(want)
    assert (got == 0.0) == (reference.choose(np.asarray(q), prev_q, cur)
                            == pick)


def test_nan_change_reads_nan():
    w0 = {"a": np.zeros(3), "b": np.zeros(2)}
    ref = {"a": np.ones(3), "b": np.ones(2)}
    gaps = reference.leaf_gaps(w0, {"a": np.ones(3),
                                    "b": np.array([1.0, np.nan])}, ref)
    assert np.isnan(gaps["change_gap"]) and np.isnan(gaps["change_diff"])
    assert reference.leaf_gaps(w0, ref, ref) == {"change_gap": 0.0,
                                                 "change_diff": 0.0}


def test_a_traced_run_reads_the_table_of_its_own_program(monkeypatch):
    """The op table comes from the window's simulation: no second one is
    built for it."""
    from repro.federated import FederatedSimulation

    cell = tiny_cell("mnist_fedavg_e5")
    built, asked = [], []
    real_build, real_layers = harness.build_sim, FederatedSimulation.op_layers

    def build(*a, **kw):
        built.append(real_build(*a, **kw))
        return built[-1]

    def op_layers(self):
        asked.append(self)
        return real_layers(self)

    monkeypatch.setattr(harness, "build_sim", build)
    monkeypatch.setattr(FederatedSimulation, "op_layers", op_layers)
    res = harness.execute(cell, SEED, 0.2, True, time.perf_counter(),
                          data=tiny_data(cell))
    assert res["correct"], res["checks"]
    assert len(built) == 1 and asked == built
    assert "local_train_ms_per_round" in res["metrics"]
