"""Mamba2 (SSD — state-space duality) block, pure JAX.

Implements the chunked SSD algorithm of Dao & Gu (arXiv:2405.21060):
intra-chunk computation is a masked-decay attention-like matmul (MXU
friendly), inter-chunk state is carried by a ``lax.scan`` recurrence —
exactly the quadratic/linear duality the paper describes, mapped to TPU as
chunked einsums instead of a custom CUDA scan kernel.

Also provides the O(1)-state single-token decode step used by the
``decode_32k`` / ``long_500k`` serve shapes (where SSMs shine: no KV cache
growth at all).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import dense_init, rmsnorm
from repro.utils import spans

Params = Dict[str, jax.Array]

_G = 1  # number of B/C groups (mamba2 default ngroups=1)


def ssm_init(rng: jax.Array, cfg: ArchConfig, dtype=None) -> Params:
    dt_ = dtype or cfg.param_dtype
    d = cfg.d_model
    d_in = cfg.ssm_d_inner
    H = cfg.ssm_nheads
    N = cfg.ssm_state
    conv_ch = d_in + 2 * _G * N
    ks = jax.random.split(rng, 4)
    # in_proj emits [z | x | B | C | dt]
    out_dim = 2 * d_in + 2 * _G * N + H
    return {
        "in_proj": dense_init(ks[0], d, out_dim, dt_),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, conv_ch), jnp.float32)
                   * (1.0 / cfg.ssm_conv ** 0.5)).astype(dt_),
        "conv_b": jnp.zeros((conv_ch,), dt_),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((H,), 0.01))).astype(jnp.float32),
        "norm_scale": jnp.zeros((d_in,), dt_),
        "out_proj": dense_init(ks[2], d_in, d, dt_),
    }


def _segsum(dA: jax.Array) -> jax.Array:
    """[..., L] per-step log-decays → [..., L, L] lower-tri pairwise sums.

    out[i, j] = sum_{j < t <= i} dA[t]  (i >= j), -inf above the diagonal.
    """
    L = dA.shape[-1]
    cs = jnp.cumsum(dA, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]          # [.., i, j]
    ii = jnp.arange(L)
    mask = ii[:, None] >= ii[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,      # [B, S, H, P] (pre-multiplied by nothing; dt applied here)
    dt: jax.Array,     # [B, S, H]  (post-softplus)
    A: jax.Array,      # [H] negative decay rates
    Bm: jax.Array,     # [B, S, G, N]
    Cm: jax.Array,     # [B, S, G, N]
    chunk: int,
    init_state: Optional[jax.Array] = None,   # [B, H, P, N]
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan → (y [B, S, H, P], final_state [B, H, P, N]).

    Head h reads B/C group ``h // (H // G)``. B and C stay at group width
    throughout: heads are viewed as ``[G, R]`` (R = H // G), so ``C Bᵀ``
    is formed once per group and the state products contract one shared
    B or C against all R heads of its group at once.
    """
    B_, S, H, P = x.shape
    G, N = Bm.shape[-2:]
    R = H // G
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // chunk

    # chunked views [B, nc, L, G, ...], heads split as [G, R]
    xc = x.reshape(B_, nc, chunk, G, R, P).astype(jnp.float32)
    dtc = dt.reshape(B_, nc, chunk, G, R).astype(jnp.float32)
    Bc = Bm.reshape(B_, nc, chunk, G, N).astype(jnp.float32)
    Cc = Cm.reshape(B_, nc, chunk, G, N).astype(jnp.float32)

    dA = dtc * A.reshape(G, R)                           # [B, nc, L, G, R] (<0)
    dA_cs = jnp.cumsum(dA, axis=2)                       # within-chunk cumsum
    xdt = xc * dtc[..., None]                            # dt-scaled inputs

    # ---- intra-chunk (quadratic, MXU) --------------------------------
    Ldec = jnp.exp(_segsum(jnp.moveaxis(dA, 2, -1)))     # [B, nc, G, R, L, L]
    scores = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc)    # [B, nc, G, L, S]
    y_diag = jnp.einsum("bcgrls,bcsgrp->bclgrp",
                        scores[:, :, :, None] * Ldec, xdt)

    # ---- chunk summary states ----------------------------------------
    decay_to_end = jnp.exp(dA_cs[:, :, -1:] - dA_cs)     # [B, nc, L, G, R]
    states = jnp.einsum("bclgn,bclgrp->bcgrpn", Bc, xdt * decay_to_end[..., None])

    # ---- inter-chunk recurrence (linear scan) -------------------------
    chunk_decay = jnp.exp(dA_cs[:, :, -1])               # [B, nc, G, R]
    s0 = (jnp.zeros((B_, G, R, P, N), jnp.float32) if init_state is None
          else init_state.reshape(B_, G, R, P, N).astype(jnp.float32))

    def step(carry, inp):
        st, dec = inp                                    # [B,G,R,P,N], [B,G,R]
        prev = carry
        new = prev * dec[..., None, None] + st
        return new, prev

    decs = jnp.moveaxis(chunk_decay, 1, 0)               # [nc, B, G, R]
    sts = jnp.moveaxis(states, 1, 0)                     # [nc, B, G, R, P, N]
    final, prevs = jax.lax.scan(step, s0, (sts, decs))
    prev_states = jnp.moveaxis(prevs, 0, 1)              # [B, nc, G, R, P, N]

    # ---- inter-chunk output contribution ------------------------------
    in_decay = jnp.exp(dA_cs)                            # decay from chunk start
    y_off = (jnp.einsum("bclgn,bcgrpn->bclgrp", Cc, prev_states)
             * in_decay[..., None])

    y = (y_diag + y_off).reshape(B_, Sp, H, P)[:, :S]
    return y, final.reshape(B_, H, P, N)


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv1d: x [B, S, C], w [W, C] → [B, S, C]."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(W):
        out = out + xp[:, i : i + x.shape[1]].astype(jnp.float32) * w[i].astype(jnp.float32)
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def _split_proj(cfg: ArchConfig, proj: jax.Array):
    d_in, N, H = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_nheads
    z, xs, Bf, Cf, dt = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + _G * N, 2 * d_in + 2 * _G * N], axis=-1
    )
    return z, xs, Bf, Cf, dt


def ssm_apply(
    params: Params, cfg: ArchConfig, x: jax.Array,
    state: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Full-sequence (train/prefill) mamba2 block. x: [B, S, D].

    If ``state`` is given, final SSM + conv states are returned for decode
    handoff; initial state is taken from it (zeros at prefill start).
    """
    B, S, D = x.shape
    d_in, N, H, P = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim

    proj = x @ params["in_proj"]
    z, xs, Bf, Cf, dt_raw = _split_proj(cfg, proj)

    conv_in = jnp.concatenate([xs, Bf, Cf], axis=-1)
    conv_out = jax.nn.silu(_causal_conv(conv_in, params["conv_w"], params["conv_b"]))
    xs, Bf, Cf = jnp.split(conv_out, [d_in, d_in + _G * N], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    xh = xs.reshape(B, S, H, P)
    Bm = Bf.reshape(B, S, _G, N)
    Cm = Cf.reshape(B, S, _G, N)

    init = state["ssm"] if state is not None else None
    with spans.part("ssd"):
        y, final = ssd_chunked(xh, dt, A, Bm, Cm, cfg.ssm_chunk, init)
    y = y + params["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B, S, d_in).astype(x.dtype)

    y = rmsnorm(y * jax.nn.silu(z), params["norm_scale"], cfg.norm_eps)
    out = y @ params["out_proj"]

    new_state = None
    if state is not None:
        # conv tail for decode handoff: last (W-1) channels of conv input
        W = cfg.ssm_conv
        tail = conv_in[:, -(W - 1):, :]
        pad = (W - 1) - tail.shape[1]
        if pad > 0:
            tail = jnp.pad(tail, ((0, 0), (pad, 0), (0, 0)))
        new_state = {"ssm": final, "conv": tail}
    return out, new_state


def ssm_decode_step(
    params: Params, cfg: ArchConfig, x: jax.Array,
    state: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token recurrent step. x: [B, 1, D]; state: {ssm, conv}."""
    B = x.shape[0]
    d_in, N, H, P = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim

    proj = x[:, 0] @ params["in_proj"]                   # [B, out]
    z, xs, Bf, Cf, dt_raw = _split_proj(cfg, proj)

    conv_in = jnp.concatenate([xs, Bf, Cf], axis=-1)     # [B, C]
    hist = jnp.concatenate([state["conv"], conv_in[:, None]], axis=1)  # [B, W, C]
    w = params["conv_w"].astype(jnp.float32)             # [W, C]
    conv_out = jnp.sum(hist.astype(jnp.float32) * w[None], axis=1) + params["conv_b"].astype(jnp.float32)
    conv_out = jax.nn.silu(conv_out).astype(x.dtype)
    xs, Bf, Cf = jnp.split(conv_out, [d_in, d_in + _G * N], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])  # [B, H]
    A = -jnp.exp(params["A_log"])                        # [H]
    dA = jnp.exp(dt * A[None])                           # [B, H]
    xh = xs.reshape(B, H, P).astype(jnp.float32)
    Bm = jnp.repeat(Bf.reshape(B, _G, N), H // _G, axis=1)  # [B, H, N]
    Cm = jnp.repeat(Cf.reshape(B, _G, N), H // _G, axis=1)

    st = state["ssm"].astype(jnp.float32)                # [B, H, P, N]
    st = st * dA[:, :, None, None] + (dt[..., None] * xh)[..., None] * Bm[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", st, Cm) + params["D"][None, :, None] * xh
    y = y.reshape(B, d_in).astype(x.dtype)

    y = rmsnorm(y * jax.nn.silu(z), params["norm_scale"], cfg.norm_eps)
    out = (y @ params["out_proj"])[:, None]              # [B, 1, D]
    new_state = {"ssm": st.astype(state["ssm"].dtype), "conv": hist[:, 1:]}
    return out, new_state
