"""Selection-policy subsystem: protocol, four policies, engine threading.

Covers the selection layer at three levels:

* policy unit tests on synthetic fleets — uniform golden equivalence vs
  the raw sampler, bias/deadline/oracle behavior, avoid-mask contracts,
* the deadline property: scores are monotone non-increasing in predicted
  completion time, all else equal,
* engine threading — ``selection=UniformPolicy()`` reproduces the
  pre-refactor golden trajectory bit for bit, the legacy
  ``bias_sampling`` flag equals an explicit :class:`BiasPolicy`, and an
  all-in-flight round is a no-op,
* the sampler clamp regression (over-drawing used to silently truncate
  the uniform path and crash the weighted one).
"""
import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _helpers import init_mlp_params, mlp_accuracy, mlp_loss
from _propcheck import given, settings, st
from repro.core import AggregationConfig
from repro.data.synthetic import make_synth_femnist
from repro.federated import (
    BiasPolicy,
    BufferedAsyncStrategy,
    DeadlineAwarePolicy,
    OracleCompletionPolicy,
    ScenarioConfig,
    SelectionContext,
    UniformPolicy,
    completion_time,
    make_fleet,
    make_policy,
    round_participation,
    sample_clients_jax,
)
from repro.federated.sampler import num_selected
from repro.federated.simulation import FederatedSimulation, FedSimConfig

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden",
                      "engine_uniform.json")

K = 16


@pytest.fixture(scope="module")
def fleet():
    return make_fleet(ScenarioConfig(preset="tiered-fleet", seed=0), K)


@pytest.fixture(scope="module")
def small_data():
    return make_synth_femnist(num_clients=16, mean_samples=24, seed=3)


@pytest.fixture(scope="module")
def mlp_params():
    return init_mlp_params(jax.random.key(0), hidden=48)


def _ctx(key, fleet=None, n=4, rnd=1, last_sync=None, avoid=None,
         time_key=None, num_clients=K):
    return SelectionContext(
        key=key, num_clients=num_clients, n=n,
        rnd=jnp.asarray(rnd, jnp.int32),
        last_sync=(jnp.zeros((num_clients,), jnp.int32)
                   if last_sync is None else last_sync),
        fleet=fleet, avoid=avoid,
        time_key=(jax.random.fold_in(key, 99) if time_key is None
                  else time_key),
    )


# ---------------------------------------------------------------------------
# UniformPolicy: bit-for-bit the raw sampler
# ---------------------------------------------------------------------------

class TestUniformPolicy:
    def test_matches_sampler_bitforbit(self):
        for seed in range(8):
            key = jax.random.key(seed)
            sel, dt = UniformPolicy().select(_ctx(key))
            np.testing.assert_array_equal(
                np.asarray(sel), np.asarray(sample_clients_jax(key, K, 4)))
            assert dt is None

    def test_matches_sampler_with_avoid(self):
        avoid = jnp.zeros((K,)).at[jnp.asarray([0, 3, 7])].set(1.0)
        for seed in range(4):
            key = jax.random.key(seed)
            sel, _ = UniformPolicy().select(_ctx(key, avoid=avoid))
            np.testing.assert_array_equal(
                np.asarray(sel),
                np.asarray(sample_clients_jax(key, K, 4, avoid=avoid)))

    def test_engine_golden_bitforbit(self, small_data, mlp_params):
        """An explicit ``selection=UniformPolicy()`` reproduces the
        pre-refactor selection trajectory bit for bit (the same golden
        the engine regression uses)."""
        with open(GOLDEN) as f:
            golden = json.load(f)
        g = golden["config"]
        cfg = FedSimConfig(
            fraction=g["fraction"], batch_size=g["batch_size"],
            local_epochs=g["local_epochs"], lr=g["lr"],
            max_rounds=g["max_rounds"], eval_every=g["eval_every"],
            aggregation=AggregationConfig(priority=tuple(g["priority"])),
            scenario=ScenarioConfig(preset=g["preset"]),
            selection=UniformPolicy(),
        )
        sim = FederatedSimulation(small_data, mlp_params, mlp_loss,
                                  mlp_accuracy, cfg)
        res = sim.run(targets=(0.99,), device_fracs=(0.99,), verbose=False)
        assert [float(m.global_acc) for m in res.metrics] == \
            golden["global_acc"]
        assert [float(m.weights_entropy) for m in res.metrics] == \
            golden["weights_entropy"]


# ---------------------------------------------------------------------------
# BiasPolicy
# ---------------------------------------------------------------------------

class TestBiasPolicy:
    def test_matches_weighted_sampler(self, fleet):
        for seed in range(4):
            key = jax.random.key(seed)
            sel, _ = BiasPolicy().select(_ctx(key, fleet))
            np.testing.assert_array_equal(
                np.asarray(sel),
                np.asarray(sample_clients_jax(
                    key, K, 4, fleet.expected_availability())))

    def test_requires_fleet(self, small_data, mlp_params):
        with pytest.raises(ValueError, match="fleet"):
            FederatedSimulation(
                small_data, mlp_params, mlp_loss, mlp_accuracy,
                FedSimConfig(max_rounds=1, selection=BiasPolicy()))

    def test_legacy_bias_sampling_flag_equivalent(self, small_data,
                                                  mlp_params):
        """``ScenarioConfig(bias_sampling=True)`` and an explicit
        ``BiasPolicy()`` produce the same trajectory."""
        def run(**kw):
            cfg = FedSimConfig(
                fraction=0.25, batch_size=8, local_epochs=1, lr=0.1,
                max_rounds=4, eval_every=2,
                aggregation=AggregationConfig(priority=(2, 0, 1)), **kw)
            sim = FederatedSimulation(small_data, mlp_params, mlp_loss,
                                      mlp_accuracy, cfg)
            res = sim.run(targets=(0.99,), device_fracs=(0.99,),
                          verbose=False)
            return [m.global_acc for m in res.metrics]

        legacy = run(scenario=ScenarioConfig(preset="mobile-heavy",
                                             bias_sampling=True))
        explicit = run(scenario=ScenarioConfig(preset="mobile-heavy"),
                       selection=BiasPolicy())
        assert legacy == explicit


# ---------------------------------------------------------------------------
# DeadlineAwarePolicy
# ---------------------------------------------------------------------------

class TestDeadlineAwarePolicy:
    @settings(max_examples=25)
    @given(st.floats(1.0, 16.0), st.floats(0.0, 16.0))
    def test_scores_monotone_in_predicted_completion(self, slow, delta):
        """Raising one client's predicted completion time never raises
        its selection score, all else equal."""
        def score0(s0):
            fleet = make_fleet(ScenarioConfig(preset="uniform"), 4)
            fleet = replace(fleet,
                            slowdown=jnp.asarray([s0, 1.0, 2.0, 4.0]))
            pol = DeadlineAwarePolicy()
            return float(pol.scores(_ctx(jax.random.key(0), fleet,
                                         num_clients=4))[0])

        assert score0(slow + delta) <= score0(slow) + 1e-6

    def test_stale_clients_pulled_back_in(self, fleet):
        """The staleness bonus strictly raises a client's score."""
        pol = DeadlineAwarePolicy()
        fresh = pol.scores(_ctx(jax.random.key(0), fleet, rnd=20))
        sync0 = jnp.zeros((K,), jnp.int32).at[5].set(19)
        mixed = pol.scores(_ctx(jax.random.key(0), fleet, rnd=20,
                                last_sync=sync0))
        # client 5 just synced -> its score drops; everyone else unchanged
        assert float(mixed[5]) < float(fresh[5])
        np.testing.assert_allclose(np.asarray(mixed[:5]),
                                   np.asarray(fresh[:5]), rtol=1e-6)

    def test_zero_temperature_picks_fastest(self, fleet):
        pol = DeadlineAwarePolicy(temperature=0.0, staleness_weight=0.0)
        sel, _ = pol.select(_ctx(jax.random.key(0), fleet, n=4))
        slow = np.asarray(fleet.slowdown)
        picked = slow[np.asarray(sel)]
        # deterministic top-k: nobody outside the pick is strictly faster
        assert picked.max() <= slow.min() + 1e-6 or \
            (slow < picked.max()).sum() <= 4

    def test_respects_avoid(self, fleet):
        avoid = jnp.zeros((K,)).at[jnp.asarray([1, 2])].set(1.0)
        for seed in range(4):
            sel, _ = DeadlineAwarePolicy().select(
                _ctx(jax.random.key(seed), fleet, avoid=avoid))
            assert not ({1, 2} & set(np.asarray(sel).tolist()))

    def test_respects_avoid_at_low_temperature(self, fleet):
        """Regression: the avoid shift must dominate the score spread at
        any temperature (a fixed penalty lost to u/T for small T)."""
        avoid = jnp.zeros((K,)).at[jnp.asarray([0, 1, 2, 3])].set(1.0)
        pol = DeadlineAwarePolicy(temperature=0.05)
        for seed in range(6):
            sel, _ = pol.select(
                _ctx(jax.random.key(seed), fleet, avoid=avoid, rnd=30))
            assert not ({0, 1, 2, 3} & set(np.asarray(sel).tolist()))

    def test_registered_criteria_mix_in(self, fleet):
        base = DeadlineAwarePolicy()
        crit = DeadlineAwarePolicy(criteria=("availability",))
        u0 = base.scores(_ctx(jax.random.key(0), fleet))
        u1 = crit.scores(_ctx(jax.random.key(0), fleet))
        assert u0.shape == u1.shape == (K,)
        assert not np.allclose(np.asarray(u0), np.asarray(u1))

    def test_works_without_fleet(self):
        sel, dt = DeadlineAwarePolicy().select(_ctx(jax.random.key(0)))
        assert sel.shape == (4,) and dt is None


# ---------------------------------------------------------------------------
# OracleCompletionPolicy
# ---------------------------------------------------------------------------

class TestOraclePolicy:
    def test_returns_true_dts_of_fastest(self, fleet):
        ctx = _ctx(jax.random.key(0), fleet, n=5)
        sel, dt = OracleCompletionPolicy().select(ctx)
        dt_all = np.asarray(completion_time(fleet, jnp.arange(K),
                                            ctx.time_key))
        np.testing.assert_allclose(np.asarray(dt), dt_all[np.asarray(sel)],
                                   rtol=1e-6)
        # the pick IS the 5 smallest true completion times
        assert set(np.asarray(sel).tolist()) == \
            set(np.argsort(dt_all)[:5].tolist())

    def test_respects_avoid(self, fleet):
        ctx = _ctx(jax.random.key(0), fleet, n=5)
        dt_all = np.asarray(completion_time(fleet, jnp.arange(K),
                                            ctx.time_key))
        fastest = int(np.argmin(dt_all))
        avoid = jnp.zeros((K,)).at[fastest].set(1.0)
        sel, _ = OracleCompletionPolicy().select(
            _ctx(jax.random.key(0), fleet, n=5, avoid=avoid,
                 time_key=ctx.time_key))
        assert fastest not in set(np.asarray(sel).tolist())


# ---------------------------------------------------------------------------
# factory + [K] participation mask
# ---------------------------------------------------------------------------

class TestFactoryAndBridge:
    def test_make_policy(self):
        assert isinstance(make_policy("uniform"), UniformPolicy)
        assert isinstance(make_policy("bias"), BiasPolicy)
        p = make_policy("deadline", staleness_weight=2.0)
        assert p.staleness_weight == 2.0
        assert isinstance(make_policy("oracle"), OracleCompletionPolicy)

    def test_unknown_raises(self):
        with pytest.raises(KeyError):
            make_policy("round-robin")

    def test_round_participation_mask(self, fleet):
        mask = round_participation(make_policy("deadline"),
                                   jax.random.key(0), K, 6, fleet=fleet)
        m = np.asarray(mask)
        assert m.shape == (K,)
        assert set(np.unique(m).tolist()) <= {0.0, 1.0}
        assert m.sum() == 6.0

    def test_round_participation_jits(self, fleet):
        f = jax.jit(lambda k: round_participation(
            make_policy("deadline"), k, K, 6, fleet=fleet))
        np.testing.assert_array_equal(
            np.asarray(f(jax.random.key(1))),
            np.asarray(round_participation(make_policy("deadline"),
                                           jax.random.key(1), K, 6,
                                           fleet=fleet)))


# ---------------------------------------------------------------------------
# engine threading: all-in-flight no-op + sampler clamp regression
# ---------------------------------------------------------------------------

class TestEngineThreading:
    def test_all_in_flight_round_is_noop(self, small_data, mlp_params):
        """When every client's update is already buffered, the next wave
        contributes nothing: params, buffer and staleness clocks are
        unchanged (soft-excluded backfill picks must not re-enter)."""
        cfg = FedSimConfig(
            fraction=0.25, batch_size=8, local_epochs=1, lr=0.1,
            max_rounds=1,
            aggregation=AggregationConfig(
                criteria=("staleness", "Ds", "Ld", "Md"),
                priority=(0, 1, 2, 3)),
            scenario=ScenarioConfig(preset="uniform"),
            strategy=BufferedAsyncStrategy(buffer_size=64),
            # this test drives _run_one directly and re-reads the input
            # carry afterwards — opt out of carry donation (run() callers
            # get a protective copy instead)
            donate=False,
        )
        sim = FederatedSimulation(small_data, mlp_params, mlp_loss,
                                  mlp_accuracy, cfg)
        state = sim.init_state()
        state = replace(state,
                        in_buffer=jnp.ones((small_data.num_clients,),
                                           jnp.float32))
        new_state, ys = sim._run_one(state, jnp.asarray(1, jnp.int32))
        assert float(ys["participants"]) == 0.0
        assert int(new_state.buffer_count) == 0
        assert int(new_state.commits) == 0
        diff = jax.tree.map(
            lambda a, b: float(np.max(np.abs(np.asarray(a) - np.asarray(b)))),
            new_state.params, state.params)
        assert max(jax.tree.leaves(diff)) == 0.0

    def test_sampler_clamps_overdraw(self):
        """Regression: asking for more clients than exist used to return
        a silently-short uniform draw and crash the weighted path."""
        sel = np.asarray(sample_clients_jax(jax.random.key(0), 5, 9))
        assert sorted(sel.tolist()) == [0, 1, 2, 3, 4]
        w = jnp.ones((5,), jnp.float32)
        sel_w = np.asarray(sample_clients_jax(jax.random.key(0), 5, 9,
                                              weights=w))
        assert sorted(sel_w.tolist()) == [0, 1, 2, 3, 4]
        avoid = jnp.zeros((5,)).at[0].set(1.0)
        sel_a = np.asarray(sample_clients_jax(jax.random.key(0), 5, 9,
                                              avoid=avoid))
        assert sorted(sel_a.tolist()) == [0, 1, 2, 3, 4]

    def test_num_selected_clamped(self):
        assert num_selected(10, 2.0) == 10
        assert num_selected(10, 0.1) == 1
        assert num_selected(10, 0.0) == 1

    def test_all_policies_clamp_overdraw(self, fleet):
        """Every policy honours the sampler's min(n, K) contract — the
        top_k paths used to crash on n > K."""
        for name in ("uniform", "bias", "deadline", "oracle"):
            mask = round_participation(make_policy(name), jax.random.key(0),
                                       4, 9, fleet=make_fleet(
                                           ScenarioConfig(), 4))
            assert float(np.asarray(mask).sum()) == 4.0

    def test_deadline_policy_under_async_engine(self, small_data,
                                                mlp_params):
        """Policy x strategy composition: deadline selection under the
        buffered-async engine honours in-flight avoidance and learns."""
        cfg = FedSimConfig(
            fraction=0.25, batch_size=8, local_epochs=1, lr=0.1,
            max_rounds=8, eval_every=4,
            aggregation=AggregationConfig(
                criteria=("staleness", "Ds", "Ld", "Md"),
                priority=(0, 1, 2, 3)),
            scenario=ScenarioConfig(preset="tiered-fleet", seed=1),
            strategy=BufferedAsyncStrategy(buffer_size=6),
            selection=DeadlineAwarePolicy(),
        )
        sim = FederatedSimulation(small_data, mlp_params, mlp_loss,
                                  mlp_accuracy, cfg)
        res = sim.run(targets=(0.99,), device_fracs=(0.99,), verbose=False)
        assert res.metrics[-1].commits > 0
        assert all(np.isfinite(m.global_acc) for m in res.metrics)
        times = [m.sim_time for m in res.metrics]
        assert all(b > a for a, b in zip(times, times[1:]))
