"""Per-architecture smoke tests (deliverable f): every assigned arch, in a
reduced variant (2 layers, d_model<=512, <=4 experts), runs one forward /
train step on CPU with asserted output shapes and no NaNs; decode parity
against the full forward is checked for the decoder-only families."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.models.registry import bundle
from repro.models.transformer import lm_logits
from repro.utils.pytree import tree_count_params

ALL_ARCHS = sorted(ARCHS)

# Fast tier runs one small representative arch; the full per-arch sweep is
# slow-marked (reduced transformers still take 10-20s each to compile on CPU).
FAST_ARCHS = {"qwen2-0.5b"}


def _arch_params(archs):
    return [
        a if a in FAST_ARCHS else pytest.param(a, marks=pytest.mark.slow)
        for a in archs
    ]


def _batch(cfg, B=2, S=32, rng_seed=0):
    key = jax.random.key(rng_seed)
    batch = {
        "tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
    }
    if cfg.arch_type == "audio":
        batch["frames"] = jax.random.normal(
            key, (B, cfg.num_frontend_tokens, cfg.d_model), cfg.param_dtype
        ) * 0.02
    if cfg.frontend == "vision":
        batch["extra_embeds"] = jax.random.normal(
            key, (B, cfg.num_frontend_tokens, cfg.d_model), cfg.param_dtype
        ) * 0.02
        batch["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(S)[None, None], (3, B, S)
        ).astype(jnp.int32)
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_constraints(arch):
    r = ARCHS[arch].reduced()
    assert r.num_layers <= 2
    assert r.d_model <= 512
    assert r.num_experts <= 4


@pytest.mark.slow
@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_and_train_step(arch):
    cfg = ARCHS[arch].reduced()
    mdl = bundle(cfg)
    params = mdl.init(jax.random.key(0))
    batch = _batch(cfg)

    loss, metrics = jax.jit(lambda p, b: mdl.loss(p, b))(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"

    # one SGD step decreases nothing catastrophic: grads are finite
    grads = jax.grad(lambda p: mdl.loss(p, batch)[0])(params)
    gnorm = float(jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads)
    )))
    assert np.isfinite(gnorm) and gnorm > 0, f"{arch}: bad grad norm {gnorm}"

    new_params = jax.tree.map(lambda p, g: p - 0.01 * g.astype(p.dtype), params, grads)
    loss2, _ = mdl.loss(new_params, batch)
    assert bool(jnp.isfinite(loss2))


@pytest.mark.slow
@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_shapes(arch):
    cfg = ARCHS[arch].reduced()
    mdl = bundle(cfg)
    params = mdl.init(jax.random.key(1))
    B, S = 2, 16
    batch = _batch(cfg, B, S, rng_seed=1)
    batch.pop("labels")
    cache = mdl.init_cache(B, S + 4)
    logits, cache = mdl.prefill(params, batch, cache)
    assert logits.shape == (B, 1, cfg.vocab_size)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    lg, cache = mdl.decode_step(params, tok, jnp.asarray(S, jnp.int32), cache)
    assert lg.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(lg.astype(jnp.float32))))


DECODER_ONLY = [a for a in ALL_ARCHS if ARCHS[a].arch_type != "audio"]


@pytest.mark.parametrize("arch", _arch_params(DECODER_ONLY))
def test_decode_matches_full_forward(arch):
    """Teacher-forced decode logits == full-sequence forward logits."""
    cfg = ARCHS[arch].reduced()
    mdl = bundle(cfg)
    params = mdl.init(jax.random.key(2))
    B, S, P = 2, 20, 16
    toks = jax.random.randint(jax.random.key(3), (B, S), 0, cfg.vocab_size)
    full = lm_logits(params, cfg, toks).astype(jnp.float32)
    cache = mdl.init_cache(B, S)
    lg, cache = mdl.prefill(params, {"tokens": toks[:, :P]}, cache)
    errs = [float(jnp.max(jnp.abs(lg[:, 0].astype(jnp.float32) - full[:, P - 1])))]
    for t in range(P, S):
        lg, cache = mdl.decode_step(
            params, toks[:, t:t + 1], jnp.asarray(t, jnp.int32), cache
        )
        errs.append(float(jnp.max(jnp.abs(lg[:, 0].astype(jnp.float32) - full[:, t]))))
    assert max(errs) < 1e-4, f"{arch}: decode/full mismatch {max(errs)}"


@pytest.mark.slow
def test_ring_cache_decode_matches_window_attention():
    """Ring-buffer cache == full cache with window mask (long-context serving)."""
    cfg = ARCHS["qwen2-0.5b"].reduced().with_overrides(
        layer_windows=(8,), long_context_window=8,
    )
    mdl = bundle(cfg)
    params = mdl.init(jax.random.key(4))
    B, S = 1, 24
    toks = jax.random.randint(jax.random.key(5), (B, S), 0, cfg.vocab_size)

    # full-layout reference (window applied by masking)
    cache_f = mdl.init_cache(B, S, layout="full")
    lg_f, cache_f = mdl.prefill(params, {"tokens": toks[:, :16]}, cache_f,
                                layout="full")
    # ring layout: decode from scratch, feeding tokens one by one
    cfgr = cfg
    mdlr = bundle(cfgr)
    cache_r = mdlr.init_cache(B, S, layout="ring")
    lg_r = None
    for t in range(16):
        lg_r, cache_r = mdlr.decode_step(
            params, toks[:, t:t + 1], jnp.asarray(t, jnp.int32), cache_r,
            layout="ring",
        )
    np.testing.assert_allclose(
        np.asarray(lg_r[:, 0], np.float32), np.asarray(lg_f[:, 0], np.float32),
        rtol=1e-4, atol=1e-4,
    )


def test_paper_cnn_param_count():
    from repro.models.cnn import init_cnn_params

    # eval_shape: count parameters without materializing the 6.6M floats
    params = jax.eval_shape(init_cnn_params, jax.random.key(0))
    assert tree_count_params(params) == 6_603_710  # paper §3, exact
