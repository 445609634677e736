"""Per-kernel correctness: interpret-mode Pallas vs pure-jnp oracles,
swept over shapes and dtypes (deliverable c), plus property tests over
random shapes (K=1, N not a multiple of ``block_n``, bf16 storage with
f32 accumulation) through the ``_propcheck`` harness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _propcheck import given, settings, st
from repro.kernels import ref
from repro.kernels.divergence import divergence_sq
from repro.kernels.flash_attention import flash_attention
from repro.kernels.weighted_agg import weighted_agg

RNG = np.random.default_rng(42)


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("K,N", [(2, 128), (4, 1000), (16, 5000), (37, 257),
                                 (64, 8192), (1024, 1000)])
@pytest.mark.parametrize("dtype", [
    jnp.float32,
    pytest.param(jnp.bfloat16, marks=pytest.mark.slow),  # interpret-mode dup
])
def test_weighted_agg_sweep(K, N, dtype):
    x = jnp.asarray(RNG.normal(size=(K, N)), dtype)
    w = jnp.asarray(RNG.uniform(size=K), jnp.float32)
    w = w / w.sum()
    out = weighted_agg(x, w, interpret=True)
    expected = ref.weighted_agg_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("block_n", [256, 2048])
def test_weighted_agg_block_sizes(block_n):
    x = jnp.asarray(RNG.normal(size=(8, 3000)), jnp.float32)
    w = jnp.asarray(RNG.uniform(size=8), jnp.float32)
    out = weighted_agg(x, w, block_n=block_n, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.weighted_agg_ref(x, w)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("K,N", [(2, 128), (8, 4097), (32, 1024),
                                 (1024, 1000)])
@pytest.mark.parametrize("dtype", [
    jnp.float32,
    pytest.param(jnp.bfloat16, marks=pytest.mark.slow),  # interpret-mode dup
])
def test_divergence_sweep(K, N, dtype):
    x = jnp.asarray(RNG.normal(size=(K, N)), dtype)
    g = jnp.asarray(RNG.normal(size=N), dtype)
    out = divergence_sq(x, g, interpret=True)
    expected = ref.divergence_ref(x, g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=1e-2 * N if dtype == jnp.bfloat16 else 1e-3 * N)


ATTN_CASES = [
    # B, Hq, Hkv, Sq, Skv, D, causal, window, q_offset
    (2, 4, 2, 256, 256, 64, True, None, 0),      # GQA causal
    (1, 8, 1, 100, 100, 64, True, 64, 0),        # MQA + window
    (1, 4, 4, 1, 512, 64, True, None, 511),      # decode against cache
    (2, 2, 2, 128, 128, 32, False, None, 0),     # non-causal (encoder)
    (1, 6, 2, 64, 192, 128, True, None, 128),    # chunked continuation
    (1, 2, 1, 33, 65, 64, True, 16, 0),          # ragged + tiny window
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [
    jnp.float32,
    pytest.param(jnp.bfloat16, marks=pytest.mark.slow),  # interpret-mode dup
])
def test_flash_attention_sweep(case, dtype):
    B, Hq, Hkv, Sq, Skv, D, causal, window, qoff = case
    q = jnp.asarray(RNG.normal(size=(B, Hq, Sq, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, D)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_offset=qoff, block_q=64, block_k=64, interpret=True)
    expected = ref.attention_ref(q, k, v, causal=causal, window=window,
                                 q_offset=qoff)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        rtol=3e-2 if dtype == jnp.bfloat16 else 2e-5,
        atol=3e-2 if dtype == jnp.bfloat16 else 2e-5,
    )


def test_flash_attention_blocks_do_not_change_result():
    q = jnp.asarray(RNG.normal(size=(1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 256, 64)), jnp.float32)
    a = flash_attention(q, k, v, block_q=32, block_k=64, interpret=True)
    b = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# property tests: arbitrary K >= 1, N >= 1 (incl. N not a multiple of
# block_n and N < one lane row) must match the jnp oracle
# ---------------------------------------------------------------------------

def _case(K, N, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed * 1000003 + K * 1009 + N)
    x = jnp.asarray(rng.normal(size=(K, N)), dtype)
    w = jnp.asarray(rng.uniform(0.0, 1.0, size=K) + 1e-3, jnp.float32)
    g = jnp.asarray(rng.normal(size=N), dtype)
    return x, w / w.sum(), g


@settings(max_examples=8)
@given(st.integers(1, 9), st.integers(1, 700))
def test_weighted_agg_property(K, N):
    x, w, _ = _case(K, N)
    out = weighted_agg(x, w, block_n=256, interpret=True)
    assert out.shape == (N,)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.weighted_agg_ref(x, w)),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=8)
@given(st.integers(1, 9), st.integers(1, 700))
def test_divergence_property(K, N):
    x, _, g = _case(K, N, seed=1)
    out = divergence_sq(x, g, block_n=256, interpret=True)
    assert out.shape == (K,) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.divergence_ref(x, g)),
                               rtol=1e-4, atol=1e-3 * max(N, 1))


def test_weighted_agg_k1_identity():
    """K=1 with weight 1.0 is the identity — the degenerate fleet."""
    x = jnp.asarray(RNG.normal(size=(1, 300)), jnp.float32)
    out = weighted_agg(x, jnp.ones((1,), jnp.float32), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x[0]),
                               rtol=1e-6, atol=1e-6)


def test_divergence_k1_against_self_is_zero():
    g = jnp.asarray(RNG.normal(size=257), jnp.float32)
    out = divergence_sq(g[None], g, interpret=True)
    np.testing.assert_allclose(np.asarray(out), [0.0], atol=1e-6)


def test_block_n_clamped_to_input_width():
    """A default (2048) block on a 130-wide input must not blow up the
    grid math — block_n is clamped to the lane-aligned need."""
    x = jnp.asarray(RNG.normal(size=(4, 130)), jnp.float32)
    w = jnp.asarray([0.25] * 4, jnp.float32)
    g = jnp.asarray(RNG.normal(size=130), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(weighted_agg(x, w, block_n=2048, interpret=True)),
        np.asarray(ref.weighted_agg_ref(x, w)), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(divergence_sq(x, g, block_n=2048, interpret=True)),
        np.asarray(ref.divergence_ref(x, g)), rtol=1e-4, atol=1e-3)


def test_bf16_storage_f32_accumulation():
    """bf16 inputs accumulate in f32: a long reduction stays within f32
    tolerance of the f32-upcast oracle (pure-bf16 accumulation would be
    off by orders of magnitude at N=4096)."""
    N = 4096
    x = jnp.asarray(RNG.normal(size=(3, N)), jnp.bfloat16)
    w = jnp.asarray([0.5, 0.3, 0.2], jnp.float32)
    g = jnp.asarray(RNG.normal(size=N), jnp.bfloat16)

    agg = weighted_agg(x, w, interpret=True)
    assert agg.dtype == jnp.bfloat16          # storage dtype preserved
    np.testing.assert_allclose(
        np.asarray(agg, np.float32),
        np.asarray(ref.weighted_agg_ref(x, w), np.float32),
        rtol=2e-2, atol=2e-2)

    div = divergence_sq(x, g, interpret=True)
    assert div.dtype == jnp.float32           # accumulator dtype exposed
    # the oracle upcasts to f32 before reducing; matching it tightly
    # (relative to the ~8e3 magnitude of the sums) proves the kernel
    # did not accumulate in bf16
    np.testing.assert_allclose(np.asarray(div),
                               np.asarray(ref.divergence_ref(x, g)),
                               rtol=1e-3)


def test_tree_ops_match():
    from repro.kernels.ops import tree_divergence_sq, tree_weighted_agg
    from repro.utils.pytree import tree_weighted_sum

    stacked = {
        "big": jnp.asarray(RNG.normal(size=(4, 513)), jnp.float32),
        "small": jnp.asarray(RNG.normal(size=(4, 7)), jnp.float32),
    }
    w = jnp.asarray([0.1, 0.2, 0.3, 0.4], jnp.float32)
    out = tree_weighted_agg(stacked, w)
    expected = tree_weighted_sum(stacked, w)
    for key in stacked:
        np.testing.assert_allclose(np.asarray(out[key]), np.asarray(expected[key]),
                                   rtol=2e-5, atol=2e-5)

    g = {"big": jnp.zeros((513,)), "small": jnp.zeros((7,))}
    div = tree_divergence_sq(stacked, g)
    expected_div = sum(
        np.sum(np.asarray(stacked[k]) ** 2, axis=1) for k in stacked
    )
    np.testing.assert_allclose(np.asarray(div), expected_div, rtol=1e-4)


@pytest.mark.parametrize("case", [
    (2, 4, 2, 100, 100, 32, True, None, 0, 32),
    (1, 8, 1, 64, 200, 64, True, 48, 136, 64),
    (2, 2, 2, 50, 50, 32, False, None, 0, 16),
])
def test_attention_chunked_matches_ref(case):
    """Online-softmax XLA-level flash == reference attention."""
    B, Hq, Hkv, Sq, Skv, D, causal, win, qoff, block = case
    q = jnp.asarray(RNG.normal(size=(B, Hq, Sq, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, D)), jnp.float32)
    a = ref.attention_chunked(q, k, v, causal=causal, window=win,
                              q_offset=qoff, block=block)
    b = ref.attention_ref(q, k, v, causal=causal, window=win, q_offset=qoff)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


def test_attention_chunked_k_valid():
    """k_valid masks cache positions beyond the prefill length."""
    q = jnp.asarray(RNG.normal(size=(1, 2, 8, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 64, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 64, 32)), jnp.float32)
    a = ref.attention_chunked(q, k, v, causal=True, block=16, k_valid=8)
    b = ref.attention_ref(q, k[:, :, :8], v[:, :, :8], causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_model_level_chunked_attention_equivalence():
    """attn_block config produces identical logits (train + prefill)."""
    from repro.configs.registry import ARCHS
    from repro.models.registry import bundle
    from repro.models.transformer import lm_logits

    cfg = ARCHS["qwen2-0.5b"].reduced()
    cfgc = cfg.with_overrides(attn_block=16)
    mdl, mdlc = bundle(cfg), bundle(cfgc)
    params = mdl.init(jax.random.key(1))
    toks = jax.random.randint(jax.random.key(2), (2, 48), 0, cfg.vocab_size)
    np.testing.assert_allclose(
        np.asarray(lm_logits(params, cfg, toks)),
        np.asarray(lm_logits(params, cfgc, toks)), rtol=1e-4, atol=1e-4)
    lg_f, _ = mdl.prefill(params, {"tokens": toks}, mdl.init_cache(2, 48))
    lg_c, _ = mdlc.prefill(params, {"tokens": toks}, mdlc.init_cache(2, 48))
    np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_c),
                               rtol=1e-4, atol=1e-4)
