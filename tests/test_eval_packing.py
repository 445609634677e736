"""Evaluation over the packed test rows.

``FederatedSimulation`` packs every client's real test rows into one
``[T]`` batch at construction and sums per-row scores per client.  These
tests hold it to the padded computation it replaced (``acc_fn`` vmapped
over the ``[K, max_t]`` test tensor with a mask zeroing the padding):
per-client and global accuracies bit for bit on the pytree, flat and mesh
paths, and the same Algorithm-1 choices in a run; and the rows run a
block at a time (``eval_block``) bit for bit as in one batch.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import make_synth_femnist
from repro.federated.simulation import FederatedSimulation, FedSimConfig
from repro.launch.mesh import make_host_mesh
from repro.models.cnn import cnn_accuracy, cnn_loss, init_cnn_params
from repro.models.mlp import init_mlp_params, mlp_accuracy, mlp_loss

MODELS = {
    "mlp": (lambda key: init_mlp_params(key, hidden=16), mlp_loss,
            mlp_accuracy),
    "cnn": (lambda key: init_cnn_params(key, hidden=16), cnn_loss,
            cnn_accuracy),
}


def padded_eval(data, acc_fn, params):
    """The padded-mask evaluation: every client's ``max_t`` rows, the
    padding zeroed by the mask."""
    counts = jnp.asarray(data.test_counts)
    max_t = data.test_labels.shape[1]
    mask = (jnp.arange(max_t)[None, :] < counts[:, None]).astype(jnp.float32)
    accs = jax.vmap(lambda x, y, m: acc_fn(params, x, y, m))(
        jnp.asarray(data.test_images), jnp.asarray(data.test_labels), mask)
    w = counts.astype(jnp.float32)
    return accs, jnp.sum(accs * w) / jnp.sum(w)


class PaddedSimulation(FederatedSimulation):
    """The simulation with the padded evaluation in every caller's place."""

    def _eval_global(self, params):
        return padded_eval(self.data, self.acc_fn, params)


@pytest.fixture(scope="module")
def ragged():
    data = make_synth_femnist(num_clients=8, mean_samples=30,
                              test_fraction=0.5, seed=5)
    counts = data.test_counts
    assert len(set(counts.tolist())) > 1
    assert counts.max() == data.test_labels.shape[1]
    assert counts.min() < counts.max()
    return data


def _config(**kw):
    base = dict(fraction=0.5, batch_size=8, local_epochs=1, lr=0.1,
                max_rounds=2, eval_every=1, seed=3)
    base.update(kw)
    return FedSimConfig(**base)


def _trained(data, model, cfg):
    """A model two rounds in, so accuracies are not all at chance."""
    init, loss_fn, acc_fn = MODELS[model]
    sim = FederatedSimulation(data, init(jax.random.key(1)), loss_fn, acc_fn,
                              cfg)
    res = sim.run(targets=(0.99,), device_fracs=(0.99,), verbose=False)
    return sim, res.final_params


def _assert_bitwise(got, want):
    accs, acc = got
    ref_accs, ref_acc = want
    np.testing.assert_array_equal(np.asarray(accs), np.asarray(ref_accs))
    assert np.asarray(acc).tobytes() == np.asarray(ref_acc).tobytes()


@pytest.mark.parametrize("model", ["mlp", "cnn"])
@pytest.mark.parametrize("flat", [False, True], ids=["pytree", "flat"])
def test_packed_eval_matches_padded_bitwise(ragged, model, flat):
    sim, params = _trained(ragged, model, _config(flat_params=flat))
    x = sim._fspec.ravel(params) if flat else params
    got = jax.jit(sim._eval_params)(x)
    want = jax.jit(lambda p: padded_eval(ragged, sim.acc_fn, p))(params)
    _assert_bitwise(got, want)
    assert 0.0 < float(got[1]) < 1.0


@pytest.mark.parametrize("block", [1, 3, 7])
def test_blocked_eval_matches_one_batch_bitwise(ragged, block):
    sim, params = _trained(ragged, "cnn", _config(flat_params=True))
    assert sim.eval_rows % 3 and sim.eval_rows % 7    # a remainder block
    blocked = FederatedSimulation(ragged, params, sim.loss_fn, sim.acc_fn,
                                  _config(flat_params=True, eval_block=block))
    x = sim._fspec.ravel(params)
    _assert_bitwise(jax.jit(blocked._eval_params)(x),
                    jax.jit(sim._eval_params)(x))
    text = jax.jit(blocked._eval_params).lower(x).as_text()
    assert "while" in text       # the blocks run one after another


def test_eval_block_must_be_positive(ragged):
    init, loss_fn, acc_fn = MODELS["mlp"]
    with pytest.raises(ValueError, match="eval_block"):
        FederatedSimulation(ragged, init(jax.random.key(0)), loss_fn, acc_fn,
                            _config(eval_block=0))


def test_packed_eval_matches_padded_on_mesh(ragged):
    cfg = _config(flat_params=True, mesh=make_host_mesh(), eval_every=2,
                  online_adjust=True)
    sim, params = _trained(ragged, "mlp", cfg)
    got = jax.jit(sim._eval_params)(sim._fspec.ravel(params))
    want = jax.jit(lambda p: padded_eval(ragged, sim.acc_fn, p))(params)
    _assert_bitwise(got, want)


def test_eval_rows_counts_real_rows(ragged):
    init, loss_fn, acc_fn = MODELS["mlp"]
    sim = FederatedSimulation(ragged, init(jax.random.key(0)), loss_fn,
                              acc_fn, _config())
    counts = ragged.test_counts
    assert sim.eval_rows == int(counts.sum())
    assert sim.eval_rows < counts.size * ragged.test_labels.shape[1]
    np.testing.assert_array_equal(
        np.asarray(sim._t_owner), np.repeat(np.arange(counts.size), counts))
    # each client's first test_counts[k] rows, in client order
    k = int(np.argmax(counts))
    lo = int(counts[:k].sum())
    np.testing.assert_array_equal(
        np.asarray(sim._t_rows[lo:lo + counts[k]]),
        ragged.test_images[k, :counts[k]])
    np.testing.assert_array_equal(
        np.asarray(sim._t_row_labels[lo:lo + counts[k]]),
        ragged.test_labels[k, :counts[k]])


def test_equal_counts_run_every_row(ragged):
    max_t = ragged.test_labels.shape[1]
    k = ragged.num_clients
    equal = dataclasses.replace(
        ragged, test_counts=np.full(k, max_t, np.int32))
    init, loss_fn, acc_fn = MODELS["mlp"]
    sim = FederatedSimulation(equal, init(jax.random.key(0)), loss_fn,
                              acc_fn, _config())
    assert sim.eval_rows == k * max_t
    assert sim._t_rows.shape[0] == k * max_t
    params = init(jax.random.key(2))
    _assert_bitwise(jax.jit(sim._eval_params)(params),
                    jax.jit(lambda p: padded_eval(equal, acc_fn, p))(params))


@pytest.mark.parametrize("path", ["pytree", "flat", "mesh"])
def test_online_adjust_run_matches_padded(ragged, path):
    kw = {"flat_params": path != "pytree"}
    if path == "mesh":
        kw["mesh"] = make_host_mesh()
    init, loss_fn, acc_fn = MODELS["mlp"]
    cfg = _config(online_adjust=True, max_rounds=4, **kw)
    runs = [
        cls(ragged, init(jax.random.key(1)), loss_fn, acc_fn, cfg).run(
            targets=(0.3, 0.6), device_fracs=(0.5, 0.99), verbose=False)
        for cls in (FederatedSimulation, PaddedSimulation)
    ]
    packed, padded = ([(m.round, m.global_acc, m.frac_above, m.priority,
                        m.backtracked, m.num_evaluated) for m in r.metrics]
                      for r in runs)
    assert packed == padded
    for a, b in zip(jax.tree.leaves(runs[0].final_params),
                    jax.tree.leaves(runs[1].final_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
