"""Per-layer device time and host-attributed idle (``chipbench/layers.py``):
from a traced run of each tiny cell here on the CPU, and from a
hand-built device plane laid out as a TPU trace is.

The persistent compilation cache is off in this module: its key leaves
HLO metadata out, so a round program compiled before the layer scopes
existed would be served in place of this one.
"""
import math
import time
from types import SimpleNamespace as NS

import jax
import pytest

from _tiny import SEED, harness, tiny_cell, tiny_data
from chipbench import layers
from chipbench.trace import WINDOW, Trace

CELLS = ("femnist_paper_adjust", "mnist_fedavg_e5")
NEW = ("local_train_ms_per_round", "criteria_ms_per_round",
       "aggregate_ms_per_round", "adjust_ms_per_round", "eval_ms_per_round",
       "pull_idle_ms_per_round", "dispatch_idle_ms_per_round")


@pytest.fixture(scope="module")
def traced():
    """``{cell: (cell, result, ctx)}`` of one traced run of each cell; the
    readers' shared context is caught on its way to the first reader."""
    from jax.experimental.compilation_cache import compilation_cache

    on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    out = {}
    real = harness.metric_reader
    try:
        with pytest.MonkeyPatch.context() as mp:
            for name in CELLS:
                seen = []

                def spy(metric, seen=seen):
                    mod = real(metric)
                    return NS(read=lambda ctx: (seen.append(ctx),
                                                mod.read(ctx))[1])

                mp.setattr(harness, "metric_reader", spy)
                cell = tiny_cell(name)
                res = harness.execute(cell, SEED, 0.5, True,
                                      time.perf_counter(),
                                      data=tiny_data(cell))
                out[name] = (cell, res, seen[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", on)
        compilation_cache.reset_cache()
    return out


@pytest.mark.parametrize("name", CELLS)
def test_every_new_reader_reads_a_number(traced, name):
    cell, res, _ = traced[name]
    listed = [m["name"] for m in cell["per_layer"] if m["name"] in NEW]
    assert len(listed) == (7 if name == "femnist_paper_adjust" else 6)
    for m in listed:
        assert math.isfinite(res["metrics"][m]["value"]), m
        assert res["metrics"][m]["unit"] == "ms"
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_layers_and_unscoped_sum_to_the_op_self_time(traced, name):
    _, _, ctx = traced[name]
    tr = ctx["trace"]
    module, tab = ctx["op_layers"]
    assert module == "jit_run_block"
    secs = layers.layer_seconds(tr, module, tab)
    total = sum(e.self_ns for e in tr.ops()) * 1e-9 / len(tr.devices)
    assert sum(secs.values()) == pytest.approx(total, rel=1e-6)
    # the trace names the block's ops as the compiled text does
    ops = [e for e in tr.ops() if e.stats.get("hlo_module") == module]
    assert ops and all(e.op in tab for e in ops)
    assert secs["local_train"] > 0 and secs["eval"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_adjust_time_only_where_algorithm_1_is_on(traced, name):
    cell, _, ctx = traced[name]
    adjust = layers.device_ms_per_round(ctx)["adjust"]
    if cell["traffic"]["online_adjust"]:
        assert adjust > 0
    else:
        assert adjust == 0.0


@pytest.mark.parametrize("name", CELLS)
def test_host_idle_is_within_the_idle(traced, name):
    _, res, ctx = traced[name]
    tr = ctx["trace"]
    idle = layers.idle_seconds(tr)
    total = tr.window_s - tr.busy_s()
    assert sum(idle.values()) == pytest.approx(total, rel=1e-6)
    assert idle.get("pull", 0.0) + idle.get("dispatch", 0.0) <= total
    per_round = 1e3 * total / ctx["rounds"]
    m = res["metrics"]
    assert (m["pull_idle_ms_per_round"]["value"]
            + m["dispatch_idle_ms_per_round"]["value"]) <= per_round + 1e-9


@pytest.mark.parametrize("name", CELLS)
def test_one_pull_span_per_block_with_its_round(traced, name):
    _, _, ctx = traced[name]
    host = ctx["trace"].host
    rounds = ctx["rounds"]              # one round per block
    for span in ("fedsim.block", "fedsim.dispatch", "fedsim.pull"):
        assert len([h for h in host if h.name == span]) == rounds, span
    pulls = [h for h in host if h.name == "fedsim.pull"]
    assert [h.stats["round"] for h in pulls] == list(range(1, rounds + 1))


# -- a hand-built trace laid out as a TPU's --------------------------------
def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=stats)


def _plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=e) for n, e in lines])


def _tpu_trace():
    """Window [1000, 11000).  Device busy [1000, 3000), [5000, 9000);
    idle [3000, 5000) and [9000, 11000).  Host: dispatch [2000, 4000),
    pull [4000, 10000) inside a block [1500, 10500)."""
    host = _plane("/host:CPU", [("python3", [
        _ev(WINDOW, 1000, 10000),
        _ev("fedsim.block", 1500, 9000, step_num=0),
        _ev("fedsim.dispatch", 2000, 2000, round=1),
        _ev("fedsim.pull", 4000, 6000, round=1)])])
    dev = _plane("/device:TPU:0", [
        ("XLA Ops", [_ev("%fusion.1 = f32[4] fusion()", 1000, 2000),
                     _ev("%while.2 = (f32[4]) while()", 5000, 4000),
                     _ev("%fusion.3 = f32[4] fusion()", 5000, 1000),
                     _ev("%divergence_sq.4 = f32[37,1] custom-call()",
                         6000, 500),
                     _ev("%copy.5 = f32[4] copy()", 7000, 2000)]),
        ("XLA Modules", [_ev("jit_run_block(7)", 1000, 8000)])])
    return Trace([host, dev])


def test_idle_split_is_time_weighted_by_host_span():
    tr = _tpu_trace()
    idle = layers.idle_seconds(tr)
    # [3000, 4000) under dispatch, [4000, 5000) and [9000, 10000) under
    # pull, [10000, 10500) under the block alone, [10500, 11000) none
    assert idle == pytest.approx({"dispatch": 1e-6, "pull": 2e-6,
                                  "block": 0.5e-6, "none": 0.5e-6})
    ctx = {"trace": tr, "rounds": 2}
    assert layers.idle_ms_per_round(ctx) == pytest.approx(
        {"dispatch": 5e-4, "pull": 1e-3, "block": 2.5e-4, "none": 2.5e-4})


def test_device_time_by_layer_from_the_table():
    tr = _tpu_trace()
    tab = {"fusion.1": "local_train", "while.2": "unscoped",
           "fusion.3": "local_train", "divergence_sq.4": "criteria"}
    secs = layers.layer_seconds(tr, "jit_run_block", tab)
    # the while's own time is its body's gaps; copy.5 is not in the table
    assert secs == pytest.approx({
        "local_train": 3e-6, "criteria": 0.5e-6, "aggregate": 0.0,
        "adjust": 0.0, "eval": 0.0, "unscoped": 0.5e-6 + 2e-6})
    assert sum(secs.values()) == pytest.approx(6e-6)
    # an op of another module is unscoped, whatever its name
    tr.devices["/device:TPU:0"][0].stats["hlo_module"] = "jit_other"
    assert layers.layer_seconds(tr, "jit_run_block", tab)[
        "local_train"] == pytest.approx(1e-6)


def test_a_program_without_layers_reads_nothing(monkeypatch):
    from repro.federated import FederatedSimulation

    monkeypatch.delattr(FederatedSimulation, "op_layers")
    host = _plane("/host:CPU", [("python3", [_ev(WINDOW, 0, 100)])])
    dev = _plane("/device:TPU:0", [("XLA Ops", [
        _ev("%fusion.1 = f32[4] fusion()", 10, 50)])])
    ctx = {"trace": Trace([host, dev]), "rounds": 1}
    for m in NEW:
        assert harness.metric_reader(m).read(ctx) is None, m
