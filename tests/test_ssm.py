"""Mamba2 SSD: chunked scan vs naive sequential recurrence oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.models.ssm import ssd_chunked, ssm_apply, ssm_decode_step, ssm_init


def naive_ssd(x, dt, A, Bm, Cm):
    """Sequential SSD recurrence: state[h,p,n] += dt*B*x with exp decay."""
    B_, S, H, P = x.shape
    N = Bm.shape[-1]
    G = Bm.shape[2]
    rep = H // G
    y = np.zeros((B_, S, H, P), np.float32)
    state = np.zeros((B_, H, P, N), np.float32)
    x = np.asarray(x, np.float32)
    dt = np.asarray(dt, np.float32)
    A = np.asarray(A, np.float32)
    Bm = np.asarray(np.repeat(Bm, rep, axis=2), np.float32)
    Cm = np.asarray(np.repeat(Cm, rep, axis=2), np.float32)
    for t in range(S):
        dA = np.exp(dt[:, t] * A[None])                      # [B, H]
        state = state * dA[:, :, None, None] + (
            dt[:, t, :, None] * x[:, t]
        )[..., None] * Bm[:, t, :, None, :]
        y[:, t] = np.einsum("bhpn,bhn->bhp", state, Cm[:, t])
    return y, state


def _ssd_inputs(rng, B_, S, H, P, N, G):
    x = jnp.asarray(rng.normal(size=(B_, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(B_, S, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B_, S, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B_, S, G, N)), jnp.float32)
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("S,chunk,G,H", [
    pytest.param(16, 4, 1, 4, id="16-4"),
    pytest.param(32, 8, 1, 4, id="32-8",
                 marks=pytest.mark.slow),  # same shape family as 16/4
    pytest.param(30, 8, 1, 4, id="30-8"),      # ragged tail
    pytest.param(64, 64, 1, 4, id="64-64"),    # single chunk
    pytest.param(30, 8, 2, 8, id="30-8-G2-H8"),  # two B/C groups of 4 heads
])
def test_ssd_chunked_matches_naive(S, chunk, G, H):
    rng = np.random.default_rng(0)
    x, dt, A, Bm, Cm = _ssd_inputs(rng, 2, S, H, 8, 16, G)

    y, final = ssd_chunked(x, dt, A, Bm, Cm, chunk)
    y_ref, final_ref = naive_ssd(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final), final_ref, rtol=1e-4, atol=1e-4)


def scan_ssd(x, dt, A, Bm, Cm):
    """``naive_ssd`` as a differentiable ``lax.scan`` over time steps."""
    B_, S, H, P = x.shape
    rep = H // Bm.shape[2]
    Bh = jnp.repeat(Bm, rep, axis=2)
    Ch = jnp.repeat(Cm, rep, axis=2)

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp                            # [B,H,P], [B,H], [B,H,N] x2
        dA = jnp.exp(dt_t * A[None])
        state = state * dA[:, :, None, None] + (
            dt_t[:, :, None] * x_t)[..., None] * B_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, C_t)

    s0 = jnp.zeros((B_, H, P, Bm.shape[-1]), jnp.float32)
    final, ys = jax.lax.scan(
        step, s0, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bh, Ch)))
    return jnp.moveaxis(ys, 0, 1), final


@pytest.mark.parametrize("G,H", [(1, 4), (2, 4)])
def test_ssd_chunked_grad_matches_scan(G, H):
    """The backward pass of the chunked SSD against a sequential scan's."""
    rng = np.random.default_rng(3)
    x, dt, A, Bm, Cm = _ssd_inputs(rng, 2, 14, H, 4, 8, G)
    wy = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    wf = jnp.asarray(rng.normal(size=(2, H, 4, 8)), jnp.float32)

    def scalar(fn):
        def f(x, dt, Bm, Cm):
            y, final = fn(x, dt, A, Bm, Cm)
            return jnp.sum(y * wy) + jnp.sum(final * wf)
        return jax.grad(f, argnums=(0, 1, 2, 3))

    with jax.default_matmul_precision("highest"):
        got = scalar(lambda *a: ssd_chunked(*a, chunk=4))(x, dt, Bm, Cm)
        want = scalar(scan_ssd)(x, dt, Bm, Cm)
    for name, g, w in zip(("x", "dt", "B", "C"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def _intermediate_shapes(jaxpr):
    """Every equation output's shape in ``jaxpr`` and the jaxprs it nests."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _intermediate_shapes(sub)


def test_ssd_chunked_keeps_bc_at_group_width():
    """No value of the SSD or its gradient repeats B or C to the heads."""
    H, P, N = 8, 4, 16
    x, dt, A, Bm, Cm = _ssd_inputs(np.random.default_rng(4), 1, 12, H, P, N, 1)

    def loss(x, dt, Bm, Cm):
        y, final = ssd_chunked(x, dt, A, Bm, Cm, 6)
        return jnp.sum(y) + jnp.sum(final)

    for fn in (lambda *a: ssd_chunked(a[0], a[1], A, a[2], a[3], 6),
               jax.grad(loss, argnums=(0, 1, 2, 3))):
        shapes = list(_intermediate_shapes(jax.make_jaxpr(fn)(x, dt, Bm, Cm).jaxpr))
        assert shapes
        assert not [s for s in shapes if s[-2:] == (H, N)]


def test_ssd_init_state_continuation():
    """Splitting a sequence across two chunked calls == one call."""
    rng = np.random.default_rng(1)
    B_, S, H, P, N = 1, 24, 2, 4, 8
    x = jnp.asarray(rng.normal(size=(B_, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(B_, S, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B_, S, 1, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B_, S, 1, N)), jnp.float32)

    y_all, final_all = ssd_chunked(x, dt, A, Bm, Cm, 8)
    y1, s1 = ssd_chunked(x[:, :16], dt[:, :16], A, Bm[:, :16], Cm[:, :16], 8)
    y2, s2 = ssd_chunked(x[:, 16:], dt[:, 16:], A, Bm[:, 16:], Cm[:, 16:], 8,
                         init_state=s1)
    np.testing.assert_allclose(np.asarray(y_all[:, 16:]), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final_all), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_ssm_block_prefill_then_decode_matches_full():
    """Full-sequence ssm_apply == prefill + recurrent decode steps."""
    cfg = ARCHS["mamba2-2.7b"].reduced()
    params = ssm_init(jax.random.key(0), cfg)
    rng = np.random.default_rng(2)
    B_, S = 1, 12
    x = jnp.asarray(rng.normal(size=(B_, S, cfg.d_model)) * 0.1, jnp.float32)

    y_full, _ = ssm_apply(params, cfg, x)

    P = 8
    state = {"ssm": jnp.zeros((B_, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state)),
             "conv": jnp.zeros((B_, cfg.ssm_conv - 1,
                                cfg.ssm_d_inner + 2 * cfg.ssm_state))}
    y_pre, state = ssm_apply(params, cfg, x[:, :P], state)
    np.testing.assert_allclose(np.asarray(y_pre), np.asarray(y_full[:, :P]),
                               rtol=1e-4, atol=1e-4)
    for t in range(P, S):
        y_t, state = ssm_decode_step(params, cfg, x[:, t:t + 1], state)
        np.testing.assert_allclose(
            np.asarray(y_t[:, 0]), np.asarray(y_full[:, t]),
            rtol=1e-3, atol=1e-3,
        )
