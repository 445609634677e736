"""Smoke tests for the ``examples/`` scripts.

Every example module must import cleanly (fast tier — a renamed registry
or moved symbol shows up here, not in a user's terminal), and the
registry-driven fleet examples must *run* end to end at toy sizes: they
enumerate ``PRESETS`` / ``STRATEGIES`` instead of hard-coded lists, so a
preset or strategy added to a registry is exercised by these tests
automatically.  The heavier FEMNIST main runs under ``-m slow`` only.
"""
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES_DIR = os.path.join(ROOT, "examples")

EXAMPLE_MODULES = sorted(
    f[:-3] for f in os.listdir(EXAMPLES_DIR)
    if f.endswith(".py") and not f.startswith("_")
)


@pytest.fixture(autouse=True)
def _examples_on_path(monkeypatch):
    monkeypatch.syspath_prepend(EXAMPLES_DIR)


def _run_main(module_name, argv, monkeypatch):
    monkeypatch.setattr(sys, "argv", [module_name] + argv)
    mod = importlib.import_module(module_name)
    mod.main()


class TestImports:
    @pytest.mark.parametrize("name", EXAMPLE_MODULES)
    def test_example_imports(self, name):
        importlib.import_module(name)
        assert hasattr(sys.modules[name], "main")


class TestFleetExamples:
    def test_scenario_fleet_sweeps_preset_registry(self, tmp_path,
                                                   monkeypatch):
        from repro.federated import PRESETS

        out = tmp_path / "scenarios.json"
        _run_main("scenario_fleet",
                  ["--clients", "8", "--rounds", "2", "--hidden", "16",
                   "--block", "2", "--out", str(out)], monkeypatch)
        report = json.loads(out.read_text())
        for preset in PRESETS:
            assert preset in report, f"preset {preset!r} not swept"
        assert "byzantine+trimmed-mean" in report
        for rec in report.values():
            assert 0.0 <= rec["best_acc"] <= 1.0

    def test_scenario_fleet_faults_deadline_row(self, tmp_path,
                                                monkeypatch, capsys):
        # --faults adds the outage+deadline counterpoint: outage-preset
        # fleet under deadline rounds (over-provisioning, quorum, retry
        # backoff), with arrivals/timeouts/retries reported per round
        out = tmp_path / "scenarios_faults.json"
        _run_main("scenario_fleet",
                  ["--clients", "8", "--rounds", "2", "--hidden", "16",
                   "--block", "2", "--faults", "--deadline", "2.0",
                   "--out", str(out)], monkeypatch)
        assert "arrivals/round" in capsys.readouterr().out
        report = json.loads(out.read_text())
        assert "outage" in report          # the preset itself is swept
        rec = report["outage+deadline"]
        assert 0.0 <= rec["best_acc"] <= 1.0
        for key in ("arrivals_per_round", "timeouts_per_round", "retries",
                    "sim_time"):
            assert key in rec, f"missing fault telemetry {key!r}"
        assert rec["arrivals_per_round"] >= 0.0
        assert rec["retries"] >= 0

    def test_scenario_fleet_adaptive_counterpoint(self, tmp_path,
                                                  monkeypatch, capsys):
        # --attack colluding --strategy multi-krum swaps the hostile
        # counterpoint row to the adaptive colluding-flip payload under
        # distance-based selection (cohort auto-bumped to Krum's >= 3
        # minimum at this toy scale); --strategy clipped-dp additionally
        # reports the Rényi (epsilon, delta) budget spent
        out = tmp_path / "scenarios_mk.json"
        _run_main("scenario_fleet",
                  ["--clients", "8", "--rounds", "2", "--hidden", "16",
                   "--block", "2", "--attack", "colluding",
                   "--strategy", "multi-krum", "--out", str(out)],
                  monkeypatch)
        report = json.loads(out.read_text())
        assert "byzantine-colluding+multi-krum" in report
        assert 0.0 <= report["byzantine-colluding+multi-krum"]["best_acc"] \
            <= 1.0

        out_dp = tmp_path / "scenarios_dp.json"
        _run_main("scenario_fleet",
                  ["--clients", "8", "--rounds", "2", "--hidden", "16",
                   "--block", "2", "--attack", "colluding",
                   "--strategy", "clipped-dp", "--out", str(out_dp)],
                  monkeypatch)
        assert "privacy budget spent" in capsys.readouterr().out
        rec = json.loads(out_dp.read_text())["byzantine-colluding+clipped-dp"]
        assert rec["epsilon_spent"] is not None
        assert rec["epsilon_spent"] > 0

    def test_async_fleet_sweeps_strategy_registry(self, tmp_path,
                                                  monkeypatch):
        from repro.federated import STRATEGIES

        out = tmp_path / "async_fleet.json"
        _run_main("async_fleet",
                  ["--clients", "8", "--rounds", "2", "--hidden", "16",
                   "--block", "2", "--buffer", "2", "--out", str(out)],
                  monkeypatch)
        report = json.loads(out.read_text())
        for name in STRATEGIES:
            assert name in report, f"strategy {name!r} not swept"
            assert 0.0 <= report[name]["best_acc"] <= 1.0

    def test_async_fleet_compress_flag(self, tmp_path, monkeypatch, capsys):
        # --compress int8 routes the whole strategy sweep through the
        # quantized flat path (blockwise absmax + error feedback) and
        # reports the wire-byte reduction
        from repro.federated import STRATEGIES

        out = tmp_path / "async_fleet_q.json"
        _run_main("async_fleet",
                  ["--clients", "8", "--rounds", "2", "--hidden", "16",
                   "--block", "2", "--buffer", "2", "--compress", "int8",
                   "--out", str(out)], monkeypatch)
        assert "compress=int8" in capsys.readouterr().out
        report = json.loads(out.read_text())
        for name in STRATEGIES:
            assert name in report, f"strategy {name!r} not swept"
            assert 0.0 <= report[name]["best_acc"] <= 1.0

    def test_async_fleet_mesh_flag(self, tmp_path, monkeypatch):
        # --mesh runs the whole strategy sweep through the shard_map'd
        # flat path on the local device mesh (1 shard under tier-1 CPU;
        # the multi-shard equivalence gate lives in test_flatpath.py)
        from repro.federated import STRATEGIES

        out = tmp_path / "async_fleet_mesh.json"
        _run_main("async_fleet",
                  ["--clients", "8", "--rounds", "2", "--hidden", "16",
                   "--block", "2", "--buffer", "2", "--mesh",
                   "--out", str(out)], monkeypatch)
        report = json.loads(out.read_text())
        for name in STRATEGIES:
            assert name in report, f"strategy {name!r} not swept"
            assert 0.0 <= report[name]["best_acc"] <= 1.0


class TestLightMains:
    def test_quickstart_runs(self, monkeypatch, capsys):
        _run_main("quickstart", [], monkeypatch)
        assert capsys.readouterr().out.strip()

    @pytest.mark.parametrize("flags", [[], ["--fedavg"], ["--adjust"]],
                             ids=["prioritized", "fedavg", "adjust"])
    def test_federated_llm_runs(self, flags, monkeypatch, capsys):
        # the LM example at toy size through FederatedSimulation's flat
        # path: prioritized criteria, the FedAvg baseline, and
        # Algorithm-1's candidate sweep
        _run_main("federated_llm",
                  flags + ["--steps", "2", "--layers", "1", "--d-model", "32",
                           "--seq", "16", "--batch-per-client", "1"],
                  monkeypatch)
        assert "done" in capsys.readouterr().out


@pytest.mark.slow
class TestHeavyMains:
    def test_femnist_federated_runs(self, tmp_path, monkeypatch):
        _run_main("femnist_federated",
                  ["--clients", "8", "--rounds", "2", "--hidden", "16",
                   "--out", str(tmp_path / "femnist")], monkeypatch)
