"""Crash-recoverable server state.

Two halves:

* ``checkpoint/io.py`` hardening — restores are validated against the
  template (treedef / per-leaf shape / per-leaf dtype, errors naming the
  offending leaf), saves carry a schema-version field checked on load,
  and the round-stamped ``checkpoint_path``/``latest_checkpoint`` layout
  ignores torn ``.tmp`` writes.
* the crash-recovery gate — a subprocess run is hard-killed
  (``os._exit``) right after a mid-run block checkpoint, resumed from
  ``latest_checkpoint``, and must reproduce the uninterrupted run's
  final params and metrics **bit for bit**, on both the pytree and the
  flat server representations.  Works because every round's randomness
  folds from the absolute round index, and the checkpoint carries the
  complete engine carry (params, quality/priority, staleness clocks,
  buffers, EF residuals, virtual clock, deadline backoff) plus the run
  metadata (metrics history, targets hit, DP-accountant parameters).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import pytest

from _helpers import init_mlp_params, mlp_accuracy, mlp_loss
from repro.checkpoint import (
    SCHEMA_VERSION,
    CheckpointMismatch,
    checkpoint_path,
    latest_checkpoint,
    load_metadata,
    restore_pytree,
    restore_server_state,
    save_pytree,
    save_server_state,
)
from repro.core import AggregationConfig
from repro.data.synthetic import make_synth_femnist
from repro.federated import FederatedSimulation, FedSimConfig, ScenarioConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------
class TestRestoreHardening:
    def _tree(self):
        return {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                "b": jnp.zeros((3,), jnp.float32)}

    def test_roundtrip_carries_schema_version(self, tmp_path):
        p = str(tmp_path / "t.msgpack")
        save_pytree(p, self._tree(), metadata={"k": 1})
        with open(p, "rb") as f:
            payload = msgpack.unpackb(f.read(), raw=False)
        assert payload["schema"] == SCHEMA_VERSION
        out = restore_pytree(p, self._tree())
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(self._tree()["w"]))
        assert load_metadata(p) == {"k": 1}

    def test_legacy_file_without_schema_loads(self, tmp_path):
        """Files written before the schema field existed load as v0 —
        their payload layout is unchanged."""
        p = str(tmp_path / "legacy.msgpack")
        save_pytree(p, self._tree())
        with open(p, "rb") as f:
            payload = msgpack.unpackb(f.read(), raw=False)
        del payload["schema"]
        with open(p, "wb") as f:
            f.write(msgpack.packb(payload, use_bin_type=True))
        out = restore_pytree(p, self._tree())
        np.testing.assert_array_equal(np.asarray(out["b"]), 0.0)

    def test_newer_schema_refused(self, tmp_path):
        p = str(tmp_path / "future.msgpack")
        save_pytree(p, self._tree())
        with open(p, "rb") as f:
            payload = msgpack.unpackb(f.read(), raw=False)
        payload["schema"] = SCHEMA_VERSION + 1
        with open(p, "wb") as f:
            f.write(msgpack.packb(payload, use_bin_type=True))
        with pytest.raises(CheckpointMismatch, match="schema"):
            restore_pytree(p, self._tree())

    def test_shape_mismatch_names_leaf(self, tmp_path):
        p = str(tmp_path / "t.msgpack")
        save_pytree(p, self._tree())
        bad = dict(self._tree(), w=jnp.zeros((2, 4), jnp.float32))
        with pytest.raises(CheckpointMismatch, match=r"'w'"):
            restore_pytree(p, bad)

    def test_dtype_mismatch_names_leaf(self, tmp_path):
        p = str(tmp_path / "t.msgpack")
        save_pytree(p, self._tree())
        bad = dict(self._tree(), b=jnp.zeros((3,), jnp.int32))
        with pytest.raises(CheckpointMismatch, match=r"dtype.*'b'"):
            restore_pytree(p, bad)

    def test_treedef_mismatch_raises(self, tmp_path):
        p = str(tmp_path / "t.msgpack")
        save_pytree(p, self._tree())
        renamed = {"weight": self._tree()["w"], "b": self._tree()["b"]}
        with pytest.raises(CheckpointMismatch, match="structure"):
            restore_pytree(p, renamed)

    def test_leaf_count_mismatch_raises(self, tmp_path):
        p = str(tmp_path / "t.msgpack")
        save_pytree(p, self._tree())
        with open(p, "rb") as f:
            payload = msgpack.unpackb(f.read(), raw=False)
        payload["leaves"] = payload["leaves"][:1]
        del payload["keys"]          # force the count check to do the work
        with open(p, "wb") as f:
            f.write(msgpack.packb(payload, use_bin_type=True))
        with pytest.raises(CheckpointMismatch, match="leaves"):
            restore_pytree(p, self._tree())


class TestCheckpointLayout:
    def test_round_stamped_paths_sort(self, tmp_path):
        d = str(tmp_path)
        assert checkpoint_path(d, 42).endswith("server_state_00000042.msgpack")
        for rnd in (2, 10, 4):
            save_pytree(checkpoint_path(d, rnd), {"x": jnp.zeros(1)},
                        metadata={"round": rnd})
        assert latest_checkpoint(d) == checkpoint_path(d, 10)

    def test_latest_ignores_torn_tmp_writes(self, tmp_path):
        d = str(tmp_path)
        save_pytree(checkpoint_path(d, 4), {"x": jnp.zeros(1)})
        with open(checkpoint_path(d, 8) + ".tmp", "wb") as f:
            f.write(b"torn")
        assert latest_checkpoint(d) == checkpoint_path(d, 4)

    def test_empty_or_missing_dir(self, tmp_path):
        assert latest_checkpoint(str(tmp_path)) is None
        assert latest_checkpoint(str(tmp_path / "nope")) is None

    def test_server_state_roundtrip(self, tmp_path):
        state = {"params": jnp.arange(4, dtype=jnp.float32),
                 "clock": jnp.float32(3.5)}
        p = checkpoint_path(str(tmp_path), 6)
        save_server_state(p, state, {"round": 6, "note": "x"})
        out, meta = restore_server_state(p, state)
        np.testing.assert_array_equal(np.asarray(out["params"]),
                                      np.asarray(state["params"]))
        assert meta["round"] == 6 and meta["note"] == "x"


# ----------------------------------------------------------------------
def _sim(data, params, **kw):
    kw.setdefault("aggregation", AggregationConfig(priority=(2, 0, 1)))
    kw.setdefault("fraction", 0.34)
    kw.setdefault("batch_size", 8)
    kw.setdefault("local_epochs", 1)
    kw.setdefault("lr", 0.1)
    kw.setdefault("max_rounds", 4)
    kw.setdefault("eval_every", 2)
    return FederatedSimulation(data, params, mlp_loss, mlp_accuracy,
                               FedSimConfig(**kw))


class TestResumeValidation:
    @pytest.fixture(scope="class")
    def small_data(self):
        return make_synth_femnist(num_clients=12, mean_samples=16, seed=5)

    @pytest.fixture(scope="class")
    def mlp_params(self):
        return init_mlp_params(jax.random.key(1), hidden=16)

    @pytest.fixture(scope="class")
    def ckpt(self, small_data, mlp_params, tmp_path_factory):
        d = str(tmp_path_factory.mktemp("ckpt"))
        sim = _sim(small_data, mlp_params, checkpoint_every=2,
                   checkpoint_dir=d)
        sim.run(targets=(0.99,), device_fracs=(0.99,), verbose=False)
        path = latest_checkpoint(d)
        assert path is not None
        return path

    def test_checkpoint_every_needs_dir(self, small_data, mlp_params):
        with pytest.raises(ValueError, match="checkpoint_dir"):
            _sim(small_data, mlp_params, checkpoint_every=2)

    def test_checkpoint_every_must_align_with_blocks(self, small_data,
                                                     mlp_params):
        with pytest.raises(ValueError, match="eval_every"):
            _sim(small_data, mlp_params, checkpoint_every=3,
                 checkpoint_dir="/tmp/x", eval_every=2)

    def test_fingerprint_mismatch_refused(self, small_data, mlp_params,
                                          ckpt):
        other = _sim(small_data, mlp_params, lr=0.05)
        with pytest.raises(ValueError, match="configuration"):
            other.run(targets=(0.99,), device_fracs=(0.99,), verbose=False,
                      resume_from=ckpt)

    def test_goal_mismatch_refused(self, small_data, mlp_params, ckpt):
        sim = _sim(small_data, mlp_params)
        with pytest.raises(ValueError, match="targets"):
            sim.run(targets=(0.5,), device_fracs=(0.5,), verbose=False,
                    resume_from=ckpt)

    def test_resume_continues_bitforbit(self, small_data, mlp_params, ckpt):
        """In-process resume parity: checkpoint at round 2, resume, and
        the final trajectory equals the uninterrupted run exactly."""
        full = _sim(small_data, mlp_params).run(
            targets=(0.99,), device_fracs=(0.99,), verbose=False)
        first = checkpoint_path(os.path.dirname(ckpt), 2)
        resumed = _sim(small_data, mlp_params).run(
            targets=(0.99,), device_fracs=(0.99,), verbose=False,
            resume_from=first)
        for a, b in zip(jax.tree.leaves(full.final_params),
                        jax.tree.leaves(resumed.final_params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert full.metrics == resumed.metrics
        assert full.rounds_to_target == resumed.rounds_to_target


# ----------------------------------------------------------------------
# The crash-recovery gate: kill-and-resume in real subprocesses.

_CHILD = textwrap.dedent("""
    import sys
    mode, out, ckpt_dir, flat = sys.argv[1:5]
    flat = flat == "1"

    import jax
    jax.config.update("jax_platform_name", "cpu")
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)

    from repro.checkpoint import latest_checkpoint, save_pytree
    from repro.core import AggregationConfig
    from repro.data.synthetic import make_synth_femnist
    from repro.federated import (FederatedSimulation, FedSimConfig,
                                 ScenarioConfig)
    from repro.models.mlp import init_mlp_params, mlp_loss, mlp_accuracy

    data = make_synth_femnist(num_clients=12, mean_samples=16, seed=5)
    params = init_mlp_params(jax.random.key(1), hidden=16)
    kw = {}
    if mode != "full":
        kw = dict(checkpoint_every=2, checkpoint_dir=ckpt_dir)
    cfg = FedSimConfig(fraction=0.34, batch_size=8, local_epochs=1, lr=0.1,
                       max_rounds=6, eval_every=2,
                       aggregation=AggregationConfig(priority=(2, 0, 1)),
                       scenario=ScenarioConfig(preset="tiered-fleet", seed=0),
                       deadline=2.0, overprovision=0.5, quorum=0.25,
                       flat_params=flat, **kw)
    sim = FederatedSimulation(data, params, mlp_loss, mlp_accuracy, cfg)

    if mode == "crash":
        import os
        orig = FederatedSimulation._save_checkpoint

        def crash_after_write(self, rnd, *a, **k):
            path = orig(self, rnd, *a, **k)
            if rnd >= 4:
                os._exit(17)     # hard kill: no flush, no cleanup
            return path

        FederatedSimulation._save_checkpoint = crash_after_write
        sim.run(targets=(0.99,), device_fracs=(0.99,), verbose=False)
        sys.exit(3)              # unreachable if the kill fired

    resume = latest_checkpoint(ckpt_dir) if mode == "resume" else None
    res = sim.run(targets=(0.99,), device_fracs=(0.99,), verbose=False,
                  resume_from=resume)
    save_pytree(out, res.final_params, metadata={
        "metrics": FederatedSimulation._metrics_to_meta(res.metrics)})
""")


def _child(mode, out, ckpt_dir, flat):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORM_NAME="cpu")
    return subprocess.run(
        [sys.executable, "-c", _CHILD, mode, out, ckpt_dir,
         "1" if flat else "0"],
        env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("flat", [False, True], ids=["pytree", "flat"])
def test_kill_and_resume_is_bitforbit(tmp_path, flat):
    """The acceptance gate: a run hard-killed right after a mid-run block
    checkpoint, resumed from the latest snapshot in a *fresh process*,
    reproduces the uninterrupted run's final params and metrics bit for
    bit."""
    ckpt_dir = str(tmp_path / "ckpts")
    full_out = str(tmp_path / "full.msgpack")
    resume_out = str(tmp_path / "resumed.msgpack")

    r = _child("full", full_out, ckpt_dir, flat)
    assert r.returncode == 0, r.stderr[-2000:]

    r = _child("crash", "/dev/null", ckpt_dir, flat)
    assert r.returncode == 17, (r.returncode, r.stderr[-2000:])
    latest = latest_checkpoint(ckpt_dir)
    assert latest is not None and "00000004" in latest

    r = _child("resume", resume_out, ckpt_dir, flat)
    assert r.returncode == 0, r.stderr[-2000:]

    like = init_mlp_params(jax.random.key(1), hidden=16)
    a = restore_pytree(full_out, like)
    b = restore_pytree(resume_out, like)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert load_metadata(full_out)["metrics"] == \
        load_metadata(resume_out)["metrics"]
