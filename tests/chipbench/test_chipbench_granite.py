"""IBM's Granite-4.0-H hybrid as a federated LoRA client, at tiny widths
on the CPU: the program's mixed stack (``repro.models.mixed``) against
the plain reference of ``chipbench/models/granite_hybrid.py``, and a tiny
``granite_hybrid`` cell through ``harness.execute``, sound and with
faults planted in the timed path.

Tiny widths: hidden 64, the period ``[mamba, attention, mamba]``, 4
Mamba-2 heads of 32 with state 16, 4 query and 2 KV heads of 16, an MLP
of 96, vocabulary 97; rows of 40 tokens with chunk 16, so the scan pads
its last chunk and carries state across three chunks.
"""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _tiny import ROOT, SEED, datasets, harness
from chipbench import reference

CONFIG = harness.load_json(
    ROOT / "chipbench/configs/granite_4_0_h_micro_p1.json")
G = harness.model_module(CONFIG)
HIGHEST = jax.lax.Precision.HIGHEST

MODEL = dict(
    CONFIG["model"], hidden_size=64, shared_intermediate_size=96,
    vocab_size=97, layer_types=["mamba", "attention", "mamba"],
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
    mamba_d_head=32, mamba_n_heads=4, mamba_d_state=16, mamba_chunk_size=16,
    attention_multiplier=0.0625, lora_rank=4, lora_alpha=8, seq=40,
    test_seq=24, logits_chunk=16, eval_block=3)
MODEL["num_params"] = G.num_params(MODEL)
DATASET = {"partition": "zipf_tokens", "num_clients": 8, "num_classes": 97,
           "seq": 40, "rows": [1, 4], "rows_tail": 2.0, "test_rows": 1,
           "test_seq": 24, "prompt": 8, "zipf": 1.1, "follow": 0.5,
           "data_seed": 3}


def _weights(seed=0, b_scale=0.05):
    """The base, and adapters whose ``B`` is not zero (so they matter)."""
    key = jax.random.key(seed)
    params = G.init_params(MODEL, key)
    params = {k: v if k.endswith(".a") else b_scale * jax.random.normal(
        jax.random.fold_in(key, i), v.shape) for i, (k, v) in
        enumerate(sorted(params.items()))}
    return params, G.init_shared(MODEL, jax.random.key(seed + 1))


def _batch(b=2, t=40, prompt=5):
    toks = jax.random.randint(jax.random.key(7), (b, t + 1), 0, 97)
    labels = toks[:, 1:].at[:, :prompt].set(-1)
    return toks[:, :t], labels


def _program_logits(params, shared, toks):
    from repro.models import mixed

    m = shared.m
    h = mixed.hidden(shared.arrays, params, G._arch(m), toks,
                     m["lora_alpha"])
    emb = shared.arrays["embed"].astype(jnp.float32)
    return (h / m["logits_scaling"]) @ emb.T


@pytest.fixture
def highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- the program against the reference ---------------------------------------
def test_logits_match_the_reference(highest):
    params, shared = _weights()
    toks, _ = _batch()
    got = _program_logits(params, shared, toks)
    want = G.forward(params, toks, HIGHEST, shared=shared)
    assert got.shape == (2, 40, 97)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert float(jnp.std(want)) > 1e-2        # not a constant


def test_loss_matches_the_reference(highest):
    params, shared = _weights()
    toks, labels = _batch()
    got = G.program_loss(params, toks, labels, shared=shared)
    want = G.loss(params, toks, labels, HIGHEST, shared=shared)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_adapter_gradients_match_the_reference(highest):
    params, shared = _weights()
    toks, labels = _batch()
    got = jax.grad(G.program_loss)(params, toks, labels, shared=shared)
    want = jax.grad(G.loss)(params, toks, labels, HIGHEST, shared=shared)
    assert set(got) == set(want) == set(params)
    for k in params:
        scale = float(jnp.linalg.norm(want[k]))
        assert scale > 0, k
        assert float(jnp.linalg.norm(got[k] - want[k])) <= 1e-5 * scale, k


def test_row_scores_match_the_reference(highest):
    from repro.models import mixed

    params, shared = _weights()
    toks, labels = _batch(b=3)
    labels = labels.at[2].set(-1)                    # a row with no target
    m = shared.m
    got = mixed.row_scores(shared.arrays, params, G._arch(m), toks, labels,
                           m["lora_alpha"], m["logits_chunk"])
    want = G.row_scores(params, toks, labels, HIGHEST, shared=shared)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert float(want[2]) == 0.0 and float(want[0]) > 0.0


def test_zero_b_adapters_give_the_bases_output(highest):
    params = G.init_params(MODEL, jax.random.key(3))     # B = 0
    assert all(not np.any(v) for k, v in params.items() if k.endswith(".b"))
    _, shared = _weights()
    bare = {k: jnp.zeros_like(v) for k, v in params.items()}
    toks, _ = _batch()
    got = _program_logits(params, shared, toks)
    assert np.array_equal(got, _program_logits(bare, shared, toks))
    np.testing.assert_allclose(
        got, G.forward(bare, toks, HIGHEST, shared=shared), rtol=1e-5,
        atol=1e-6)


def test_configuration_holds_the_published_config_but_the_cut():
    from repro.configs.granite_4_0_h_micro import CONFIG as PRESET
    from repro.configs.granite_4_0_h_micro import HF

    cut = {"num_hidden_layers", "layer_types"}
    for k, v in HF.items():
        if k not in cut:
            assert CONFIG[k] == v, k
    assert CONFIG["layer_types"] == HF["layer_types"][:10]
    assert HF["layer_types"] == CONFIG["layer_types"] * 4
    assert PRESET.num_layers == 40 and PRESET.block_type == "mixed"
    assert G._arch(CONFIG["model"]) == PRESET.with_overrides(
        name=G._arch(CONFIG["model"]).name, num_layers=10,
        layer_types=PRESET.layer_types[:10])


def test_configuration_repeats_the_published_numbers_it_uses():
    m = CONFIG["model"]
    for k, v in m.items():
        if k in CONFIG:
            assert CONFIG[k] == v, k
    assert CONFIG["layer_types"] == m["layer_types"]
    assert len(m["layer_types"]) == m["num_hidden_layers"] == 10
    assert m["layer_types"].count("attention") == 1
    assert m["layer_types"][5] == "attention"
    assert G.num_params(m) == 7_205_888
    # one period 746.5M, the embedding 205.5M
    assert G.num_base_params(m) == 951_991_232


# -- the program's pieces the model needed -------------------------------------
def test_label_histogram_skips_no_target_and_is_as_wide_as_the_labels():
    from repro.data.synthetic import FederatedDataset

    rng = np.random.default_rng(0)
    labels = rng.integers(-1, 100, (3, 5, 7)).astype(np.int32)
    labels[0, :, :] = -1
    labels[1, 0, 0] = 99                       # wider than 62
    labels[2, :, 3] = -1
    counts = np.array([5, 3, 4], np.int32)
    fds = FederatedDataset(images=labels, labels=labels, counts=counts,
                           test_images=labels, test_labels=labels,
                           test_counts=counts)
    assert fds.num_classes == 100
    for k in range(3):
        h = fds.label_histogram(k)
        assert h.shape == (100,)
        real = labels[k, :counts[k]]
        assert h.sum() == np.sum(real >= 0)
        assert np.sum(h > 0) == reference.Reference._distinct(real)


def _tiny_sim(shared_as_argument: bool):
    from repro.federated import FedSimConfig, FederatedSimulation

    data = datasets.load(DATASET, cache=False)
    cell = granite_cell()
    rec = harness.recipe(cell["config"], cell["traffic"], data.counts)
    params = G.init_params(MODEL, harness.seed_key(SEED))
    shared = harness.init_shared(G, MODEL, SEED)
    if shared_as_argument:
        return harness.build_sim(cell, data, params, rec, shared), shared
    # the closed-over form a model file could only use before
    fds = harness.build_sim(cell, data, params, rec, shared).data
    loss, acc = G.program_fns()
    return FederatedSimulation(
        fds, params, lambda *a: loss(*a, shared=shared),
        lambda *a: acc(*a, shared=shared),
        FedSimConfig(fraction=0.5, batch_size=1, local_epochs=1,
                     flat_params=True)), shared


def _constants(text: str, shape: str):
    return [ln for ln in text.splitlines()
            if "constant" in ln and shape in ln]


def test_round_block_takes_the_base_as_an_argument():
    sim, shared = _tiny_sim(True)
    assert sim.shared is shared
    embed = "97x64xbf16"                   # the base's embedding
    ids = jnp.arange(1, 2, dtype=jnp.int32)
    text = sim._run_block.lower(sim.init_state(), ids,
                                sim.shared).as_text()
    assert embed in text and not _constants(text, embed)
    text = jax.jit(sim._eval_boundary).lower(
        sim.init_state().params, sim.shared).as_text()
    assert embed in text and not _constants(text, embed)
    # closed over, the same base is a constant of the block
    closed, _ = _tiny_sim(False)
    text = closed._run_block.lower(closed.init_state(), ids).as_text()
    assert _constants(text, embed)


def test_op_table_names_the_model_parts():
    sim, _ = _tiny_sim(True)
    module, tab = sim.op_layers()
    assert module == "jit_run_block"
    assert set(tab.scopes.values()) == {"mamba", "ssd", "attention", "mlp",
                                        "logits"}
    layers = {tab[op] for op in tab.scopes}
    assert {"local_train", "eval"} <= layers
    assert set(tab.scopes) <= set(tab)


# -- a tiny cell through the harness -------------------------------------------
def granite_cell() -> dict:
    cell = harness.find_cell("granite_hmicro_lora_t1024")
    config = dict(cell["config"], model=MODEL, dataset=DATASET)
    return dict(cell, config=config,
                traffic=dict(cell["traffic"], fraction=0.5))


@pytest.fixture(scope="module")
def tiny():
    cell = granite_cell()
    return cell, datasets.load(DATASET, cache=False)


def _run(cell, data):
    """One run, with the harness finding this module's model file (so
    that a fault planted in it is the one that runs)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "model_module", lambda config: G)
        return harness.execute(cell, SEED, 0.5, False, time.perf_counter(),
                               data=data)


def test_token_partition_shapes(tiny):
    _, data = tiny
    assert data.images.dtype == np.int32
    assert data.images.shape == (8, int(data.counts.max()), 40)
    assert data.test_images.shape == (8, 1, 24)
    assert (data.test_labels[:, :, :8] == -1).all()
    assert (data.test_labels[:, :, 8:] >= 0).all()
    assert set(data.counts.tolist()) <= {1, 2, 3, 4}
    # clients hold different tokens
    distinct = [len(np.unique(data.labels[k, :n]))
                for k, n in enumerate(data.counts)]
    assert len(set(distinct)) > 1


def test_sound_run_is_correct(tiny):
    res = _run(*tiny)
    assert res["correct"], res["checks"]
    assert res["checks"]["window_compiles"]["value"] == 0
    assert set(res["checks"]) >= {"acc_gap", "eval_gap", "entropy_gap",
                                  "change_gap", "change_diff"}


def test_unchanged_model_is_not_correct(tiny, monkeypatch):
    from repro.federated import engine

    step = engine.SyncStrategy.step

    def frozen(self, state, *a, **kw):
        new, ys = step(self, state, *a, **kw)
        return dataclasses.replace(new, params=state.params), ys

    monkeypatch.setattr(engine.SyncStrategy, "step", frozen)
    res = _run(*tiny)
    assert not res["correct"]
    gap = res["checks"]["change_gap"]
    assert gap["value"] == pytest.approx(1.0) and gap["value"] > gap["limit"]


def test_half_the_cohort_left_out_is_not_correct(tiny, monkeypatch):
    from repro.core import adjust
    from repro.federated import engine

    weights = adjust.compute_weights

    def half(c, cfg, priority=None, mask=None):
        keep = (jnp.arange(c.shape[0]) < c.shape[0] // 2).astype(jnp.float32)
        return weights(c, cfg, priority, keep if mask is None else mask * keep)

    monkeypatch.setattr(adjust, "compute_weights", half)
    monkeypatch.setattr(engine, "compute_weights", half)
    res = _run(*tiny)
    assert not res["correct"]
    gap = res["checks"]["entropy_gap"]
    assert gap["value"] > gap["limit"]


def test_altered_answer_is_not_correct(tiny, monkeypatch):
    acc = G.program_accuracy

    def off(*a, **kw):         # one test row in ten miscounted
        v = acc(*a, **kw)
        return v + jnp.where(v >= 0.1, -0.1, 0.1)

    monkeypatch.setattr(G, "program_accuracy", off)
    res = _run(*tiny)
    assert not res["correct"]
    gap = res["checks"]["eval_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", ["one_percent_off", "zero_scores"])
def test_answer_off_at_the_scores_scale_is_not_correct(tiny, monkeypatch,
                                                       fault):
    """An answer fault as small as the cell's scores: each row's mean
    target probability reported 1% off, or as 0."""
    acc = G.program_accuracy

    def off(*a, **kw):
        v = acc(*a, **kw)
        return v * 0.99 if fault == "one_percent_off" else 0.0 * v

    monkeypatch.setattr(G, "program_accuracy", off)
    res = _run(*tiny)
    assert not res["correct"]
    for name in ("acc_gap", "eval_gap"):
        gap = res["checks"][name]
        assert gap["value"] > gap["limit"], name


PARTS = ("ssd_ms_per_round", "mamba_ms_per_round", "attention_ms_per_round",
         "mlp_ms_per_round", "logits_ms_per_round")


def test_traced_run_reads_every_part(tiny):
    """A traced run's readers find every model part, and the parts hold
    most of local training and evaluation.  The persistent compilation
    cache is off: its key leaves the scopes out."""
    from types import SimpleNamespace as NS

    from jax.experimental.compilation_cache import compilation_cache

    cell, data = tiny
    seen = []
    real = harness.metric_reader
    on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(harness, "metric_reader", lambda name: NS(
                read=lambda ctx: (seen.append(ctx), real(name).read(ctx))[1]))
            res = harness.execute(cell, SEED, 0.5, True, time.perf_counter(),
                                  data=data)
    finally:
        jax.config.update("jax_enable_compilation_cache", on)
        compilation_cache.reset_cache()
    assert res["correct"], res["checks"]
    listed = [m["name"] for m in cell["per_layer"]]
    assert set(PARTS) <= set(listed) and "round_mfu" in listed
    # the readers of a share of a peak read nothing without a chip
    for name in listed:
        if not name.endswith(("_roofline", "_mfu")):
            assert name in res["metrics"], name
    for name in PARTS:
        assert res["metrics"][name]["value"] > 0, name
    ctx = seen[0]
    module, tab = ctx["op_layers"]
    layer_ms = {k: v for k, v in (
        (k, sum(e.self_ns for e in ctx["trace"].ops()
                if tab.get(e.op) == k)) for k in ("local_train", "eval"))}
    covered = sum(e.self_ns for e in ctx["trace"].ops()
                  if tab.get(e.op) in ("local_train", "eval")
                  and e.op in tab.scopes)
    assert covered >= 0.5 * sum(layer_ms.values())
