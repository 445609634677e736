"""The harness finds every cell's configuration, traffic mix, limits,
model file and per-layer metric readers by the names in BENCHMARK.json,
and the benchmark file keeps to its documented shapes."""
import json
import re

import numpy as np
import pytest

from _tiny import ROOT, harness, datasets

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_found_by_name(workload):
    cell = harness.find_cell(workload)
    assert cell["config"]["name"] == cell["workload"]["config"]
    assert harness.model_module(cell["config"]).forward_flops
    for m in cell["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)
    assert {m["name"] for m in cell["end_to_end"]} == {
        "updates_per_s", "peak_hbm_gib", "setup_s"}
    assert cell["limits"], "every cell has limits for its compared numbers"


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        harness.find_cell("no_such_cell")
    with pytest.raises(KeyError):
        harness.metric_reader("no_such_metric")
    with pytest.raises(KeyError):
        datasets.generate({"partition": "no_such_partition"})


def test_benchmark_file_shapes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in configs.values():
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    for w in BENCH["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_parameter_count(config):
    cfg = harness.load_json(ROOT / "chipbench" / "configs" / f"{config}.json")
    model = harness.model_module(cfg)
    assert model.num_params(cfg["model"]) == cfg["model"]["num_params"]


def test_writer_partition_is_the_programs_synthfemnist():
    from repro.data.synthetic import make_synth_femnist

    ds = {"partition": "writers", "num_clients": 6, "mean_samples": 10,
          "num_classes": 62, "classes_per_writer": [8, 24],
          "test_fraction": 0.25, "data_seed": 3}
    mine = datasets.load(ds, cache=False)
    theirs = make_synth_femnist(num_clients=6, mean_samples=10, seed=3)
    for k in ("images", "labels", "counts", "test_images", "test_labels",
              "test_counts"):
        assert np.array_equal(getattr(mine, k), getattr(theirs, k)), k


def test_label_shard_partition_gives_two_labels_per_client():
    ds = {"partition": "label_shards", "num_clients": 10, "shard_size": 30,
          "test_per_shard": 4, "num_classes": 10, "data_seed": 1}
    d = datasets.load(ds, cache=False)
    assert d.images.shape == (10, 60, 28, 28)
    assert d.test_images.shape == (10, 8, 28, 28)
    for k in range(10):
        train = set(d.labels[k].tolist())
        assert 1 <= len(train) <= 2
        assert set(d.test_labels[k].tolist()) == train


def test_recipe_sizes():
    traffic = harness.find_cell("mnist_fedavg_e5")["traffic"]
    rec = harness.recipe({"sim_seed": 0}, traffic, np.full(100, 600))
    assert (rec["S"], rec["batch_size"], rec["steps"]) == (10, 10, 300)
    traffic = harness.find_cell("femnist_paper_adjust")["traffic"]
    rec = harness.recipe({"sim_seed": 0}, traffic, np.full(371, 586))
    assert (rec["S"], rec["batch_size"], rec["steps"]) == (37, 10, 290)


def test_seed_key_keeps_high_bits():
    import jax

    a = jax.random.key_data(harness.seed_key(5))
    b = jax.random.key_data(harness.seed_key(2**33 + 5))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
