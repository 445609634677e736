"""IBM's Granite-4.0-H-Micro (``granitemoehybrid``, dense) fine-tuned by
federated clients through low-rank adapters over a frozen base.

The model block of the configuration holds the published ``config.json``
numbers the model uses, under the same keys, with ``layer_types`` cut
to the layers held here, and the adapters' ``lora_rank``, ``lora_alpha``
and targets.  Each layer is

    h  = x + m_r * mixer(rmsnorm(x))       mixer: Mamba-2 or NoPE GQA
    x' = h + m_r * mlp(rmsnorm(h))         mlp: SiLU-gated, fused gate|up

with the embedding times ``embedding_multiplier``, a final norm and
logits tied to the embedding, divided by ``logits_scaling``.  Every
adapted projection is ``x @ W + (alpha / r) (x @ A) @ B``: ``in_proj``
and ``out_proj`` of each Mamba-2 mixer, ``wq``/``wk``/``wv``/``wo``,
and the MLP's ``mlp_in`` (one adapter over ``[w_gate | w_up]``, the
checkpoint's ``input_linear``) and ``mlp_out``.

Everything but ``program_fns`` and ``make_sim`` is the benchmark's own:

* ``init_shared``: the frozen base, bfloat16, in the layout the program
  takes (runs of consecutive layers of one kind, stacked), with the
  Mamba-2 ``A_log``, ``D`` and ``dt_bias`` in float32; returned as a
  :class:`Base`, whose model block rides as static data, since the
  hooks that take the weights are not given the configuration;
* ``init_params``: the adapters, float32, each ``A`` uniform in
  ``±1/sqrt(in)`` and each ``B`` zero;
* the plain reference (``forward``, ``loss``, ``row_scores``) in
  ``jax.numpy`` at the precision it is given, in the adapters' dtype.
  Its state-space mixer is the full-length quadratic dual form of
  Mamba-2 (arXiv:2405.21060, section 6), per sequence,
  ``y = (L o C B^T) (dt x) + D x`` with ``L[i, j] = exp(sum_{j<t<=i}
  dt_t A)``, independent of the program's chunked scan.  The
  ``[tokens, vocab]`` logits and the ``[heads, T, T]`` decay matrices
  are made a block at a time, each block rematerialised;
* ``row_scores``: a row's mean probability of its target tokens (label
  ``>= 0``), which moves smoothly with the weights, where an argmax of
  random weights flips on rounding;
* ``forward_flops``/``train_flops``: operations a test row's forward
  pass and a training row's forward and backward passes require.

The program refuses to run the model where its ``FederatedSimulation``
cannot take the base as an argument: this file does not import there.
"""
from __future__ import annotations

import dataclasses
import inspect
import json
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.precision import bilinear

HIGHEST = jax.lax.Precision.HIGHEST
MAMBA, ATTENTION = "mamba", "attention"
HEAD_GROUP = 8          # heads per block of the reference's decay matrices
ROW_GROUP = 8           # test rows per block of the reference's scoring


def _program_takes_shared() -> None:
    from repro.federated import FederatedSimulation

    if "shared" not in inspect.signature(FederatedSimulation).parameters:
        raise TypeError("granite_hybrid needs a FederatedSimulation that "
                        "takes the frozen base as an argument (shared=)")


_program_takes_shared()


@jax.tree_util.register_pytree_node_class
class Base:
    """The frozen base's arrays, with the configuration's model block as
    static data (a JSON string: hashable, compared by value)."""

    def __init__(self, arrays: dict, spec: str):
        self.arrays, self.spec = arrays, spec

    @property
    def m(self) -> dict:
        return json.loads(self.spec)

    def tree_flatten(self):
        return (self.arrays,), self.spec

    @classmethod
    def tree_unflatten(cls, spec, children):
        return cls(children[0], spec)


# -- sizes -------------------------------------------------------------------
def _dims(m: dict) -> dict:
    d = m["hidden_size"]
    d_in = m["mamba_expand"] * d
    hd = m["hidden_size"] // m["num_attention_heads"]
    return {
        "d": d, "f": m["shared_intermediate_size"], "v": m["vocab_size"],
        "d_in": d_in, "n": m["mamba_d_state"], "h": m["mamba_n_heads"],
        "p": m["mamba_d_head"], "w": m["mamba_d_conv"], "hd": hd,
        "q": m["num_attention_heads"] * hd,
        "kv": m["num_key_value_heads"] * hd,
        "in_proj": 2 * d_in + 2 * m["mamba_d_state"] + m["mamba_n_heads"],
    }


def _runs(m: dict):
    out = []
    for kind in m["layer_types"]:
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])
    return [tuple(r) for r in out]


def _targets(m: dict, kind: str) -> Dict[str, tuple]:
    """``{adapter target: (in, out)}`` of a layer of ``kind``."""
    z = _dims(m)
    out = {"mlp_in": (z["d"], 2 * z["f"]), "mlp_out": (z["f"], z["d"])}
    if kind == MAMBA:
        out.update(in_proj=(z["d"], z["in_proj"]), out_proj=(z["d_in"], z["d"]))
    else:
        out.update(wq=(z["d"], z["q"]), wk=(z["d"], z["kv"]),
                   wv=(z["d"], z["kv"]), wo=(z["q"], z["d"]))
    return out


def _adapter_shapes(m: dict) -> Dict[str, tuple]:
    r = m["lora_rank"]
    out = {}
    for i, (kind, n) in enumerate(_runs(m)):
        for t, (fan_in, fan_out) in _targets(m, kind).items():
            out[f"r{i}.{t}.a"] = (n, fan_in, r)
            out[f"r{i}.{t}.b"] = (n, r, fan_out)
    return out


def _base_shapes(m: dict, kind: str, n: int) -> Dict[str, tuple]:
    z = _dims(m)
    out = {"ln1": (n, z["d"]), "ln2": (n, z["d"]),
           "w_gate": (n, z["d"], z["f"]), "w_up": (n, z["d"], z["f"]),
           "w_down": (n, z["f"], z["d"])}
    if kind == MAMBA:
        conv = z["d_in"] + 2 * z["n"]
        out.update(in_proj=(n, z["d"], z["in_proj"]),
                   conv_w=(n, z["w"], conv), conv_b=(n, conv),
                   A_log=(n, z["h"]), D=(n, z["h"]), dt_bias=(n, z["h"]),
                   norm_scale=(n, z["d_in"]),
                   out_proj=(n, z["d_in"], z["d"]))
    else:
        out.update(wq=(n, z["d"], z["q"]), wk=(n, z["d"], z["kv"]),
                   wv=(n, z["d"], z["kv"]), wo=(n, z["q"], z["d"]))
    return out


def num_params(m: dict) -> int:
    """Trained parameters: the adapters."""
    return sum(math.prod(s) for s in _adapter_shapes(m).values())


def num_base_params(m: dict) -> int:
    total = m["vocab_size"] * m["hidden_size"] + m["hidden_size"]
    for kind, n in _runs(m):
        total += sum(math.prod(s) for s in _base_shapes(m, kind, n).values())
    return total


# -- weights -----------------------------------------------------------------
def init_params(m: dict, key: jax.Array) -> Dict[str, jax.Array]:
    """The adapters: ``A`` uniform in ``±1/sqrt(in)``, ``B`` zero."""
    shapes = _adapter_shapes(m)

    def make(key):
        names = sorted(shapes)
        keys = jax.random.split(key, len(names))
        out = {}
        for name, k in zip(names, keys):
            s = shapes[name]
            if name.endswith(".a"):
                b = 1.0 / math.sqrt(s[1])
                out[name] = jax.random.uniform(k, s, jnp.float32, -b, b)
            else:
                out[name] = jnp.zeros(s, jnp.float32)
        return out

    return jax.jit(make)(key)


def _init_leaf(name: str, shape: tuple, key) -> jax.Array:
    """One frozen leaf: projections ``N(0, 1/in)``; norm weights
    ``1 + N(0, 0.1^2)`` stored as their offset from 1 (the program's
    ``rmsnorm`` multiplies by ``1 + w``); Mamba-2's initialisation of
    ``A`` (``-U[1, 16]``), ``dt`` (log-uniform in ``[1e-3, 1e-1]``) and
    ``D`` (1), and a convolution of ``N(0, 1/width)`` with a small bias."""
    f32 = jnp.float32
    if name in ("ln1", "ln2", "norm_scale", "final_norm"):
        return 0.1 * jax.random.normal(key, shape, f32)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)
    if name == "D":
        return jnp.ones(shape, f32)
    if name == "conv_b":
        return 0.1 * jax.random.normal(key, shape, f32)
    fan_in = shape[-2]
    return jax.random.normal(key, shape, f32) / math.sqrt(fan_in)


def init_shared(m: dict, key: jax.Array) -> Base:
    """The frozen base: the embedding ``N(0, 0.02^2)``, the final norm
    and every layer's leaves, bfloat16 but for Mamba-2's float32
    ``A_log``, ``D`` and ``dt_bias``."""
    runs = _runs(m)
    keep32 = ("A_log", "D", "dt_bias")

    def make(key):
        k_embed, k_norm, k_runs = jax.random.split(key, 3)
        out = {
            "embed": (0.02 * jax.random.normal(
                k_embed, (m["vocab_size"], m["hidden_size"]), jnp.float32)
            ).astype(jnp.bfloat16),
            "final_norm": _init_leaf("final_norm", (m["hidden_size"],),
                                     k_norm).astype(jnp.bfloat16),
            "runs": [],
        }
        for (kind, n), kr in zip(runs, jax.random.split(k_runs, len(runs))):
            shapes = _base_shapes(m, kind, n)
            names = sorted(shapes)
            leaves = {}
            for name, k in zip(names, jax.random.split(kr, len(names))):
                v = _init_leaf(name, shapes[name], k)
                leaves[name] = v if name in keep32 else v.astype(jnp.bfloat16)
            out["runs"].append(leaves)
        return out

    return Base(jax.jit(make)(key), json.dumps(m, sort_keys=True))


# -- the plain reference -----------------------------------------------------
def _mm(a, b, precision):
    return jnp.matmul(a, b, precision=precision)


def _einsum(spec):
    return lambda a, b, precision: jnp.einsum(spec, a, b, precision=precision)


def _rms(x, w, eps):
    """RMS norm of the last axis times ``1 + w``."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1 + w.astype(x.dtype))


def _proj(x, w, a, b, scale, precision):
    """``x @ w + scale * (x @ a) @ b``."""
    dt = x.dtype
    low = bilinear(_mm, bilinear(_mm, x, a.astype(dt), precision),
                   b.astype(dt), precision)
    return bilinear(_mm, x, w.astype(dt), precision) + scale * low


def _causal_conv(x, w, b):
    """Depthwise causal convolution along ``T``: ``y[t] = b + sum_k w[k]
    x[t - (width - 1) + k]``, with ``x[<0] = 0``."""
    width, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return b.astype(x.dtype) + sum(
        xp[:, k:k + t] * w[k].astype(x.dtype) for k in range(width))


def _ssd_quadratic(x, dt, a, bm, cm, precision):
    """The SSD's quadratic dual form over the whole sequence.

    ``x [B, T, H, P]``, ``dt [B, T, H]``, ``a [H]`` (< 0), ``bm``/``cm``
    ``[B, T, N]`` (one group); returns ``y [B, T, H, P]`` without the
    ``D`` term.  ``L[i, j]`` is made from a cumulative sum that starts
    after ``j``, so it loses nothing to the difference of two long sums.
    Heads run :data:`HEAD_GROUP` at a time."""
    b_, t, h, p = x.shape
    g = bilinear(_einsum("bin,bjn->bij"), cm, bm, precision)     # C_i . B_j
    after = jnp.tril(jnp.ones((t, t), bool), -1)                  # j < t'
    causal = jnp.tril(jnp.ones((t, t), bool))
    xdt = x * dt[..., None]
    per = math.gcd(h, HEAD_GROUP)
    groups = h // per

    def heads(xs):
        xg, dtg, ag = xs                         # [B,T,G,P], [B,T,G], [G]
        da = jnp.moveaxis(dtg * ag, 1, -1)        # [B, G, T]
        # seg[i, j] = sum_{j < t' <= i} da[t']: a cumsum over i of da[i]
        # where i > j
        seg = jnp.cumsum(jnp.where(after, da[..., :, None], 0), axis=-2)
        lmat = jnp.where(causal, jnp.exp(seg), 0)                 # [B,G,T,T]
        return bilinear(_einsum("bgij,bjgp->bigp"), lmat * g[:, None], xg,
                        precision)

    xs = (jnp.moveaxis(xdt.reshape(b_, t, groups, per, p), 2, 0),
          jnp.moveaxis(dt.reshape(b_, t, groups, per), 2, 0),
          a.reshape(groups, per))
    y = jax.lax.map(jax.checkpoint(heads), xs)     # [groups, B, T, G, P]
    return jnp.moveaxis(y, 0, 2).reshape(b_, t, h, p)


def _mamba(m, lp, ad, x, scale, precision):
    z = _dims(m)
    dt_ = x.dtype
    b_, t, _ = x.shape
    u = _proj(x, lp["in_proj"], ad["in_proj.a"], ad["in_proj.b"], scale,
              precision)
    gate = u[..., :z["d_in"]]
    xbc = u[..., z["d_in"]:2 * z["d_in"] + 2 * z["n"]]
    dt = u[..., 2 * z["d_in"] + 2 * z["n"]:]
    xbc = jax.nn.silu(_causal_conv(xbc, lp["conv_w"], lp["conv_b"]))
    xs = xbc[..., :z["d_in"]].reshape(b_, t, z["h"], z["p"])
    bm = xbc[..., z["d_in"]:z["d_in"] + z["n"]]
    cm = xbc[..., z["d_in"] + z["n"]:]
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(dt_))
    a = -jnp.exp(lp["A_log"].astype(dt_))
    y = _ssd_quadratic(xs, dt, a, bm, cm, precision)
    y = y + lp["D"].astype(dt_)[:, None] * xs
    y = y.reshape(b_, t, z["d_in"]) * jax.nn.silu(gate)
    y = _rms(y, lp["norm_scale"], m["rms_norm_eps"])
    return _proj(y, lp["out_proj"], ad["out_proj.a"], ad["out_proj.b"],
                 scale, precision)


def _attention(m, lp, ad, x, scale, precision):
    """Causal GQA without positions; scores times ``attention_multiplier``."""
    z = _dims(m)
    b_, t, _ = x.shape
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], z["hd"]
    q, k, v = (_proj(x, lp[w], ad[w + ".a"], ad[w + ".b"], scale, precision)
               for w in ("wq", "wk", "wv"))
    q = q.reshape(b_, t, hq, hd)
    k = jnp.repeat(k.reshape(b_, t, hkv, hd), hq // hkv, axis=2)
    v = jnp.repeat(v.reshape(b_, t, hkv, hd), hq // hkv, axis=2)
    s = bilinear(_einsum("bihd,bjhd->bhij"), q, k, precision)
    s = s * m["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = bilinear(_einsum("bhij,bjhd->bihd"), jax.nn.softmax(s, axis=-1), v,
                 precision)
    return _proj(o.reshape(b_, t, hq * hd), lp["wo"], ad["wo.a"], ad["wo.b"],
                 scale, precision)


def _mlp(m, lp, ad, x, scale, precision):
    f = m["shared_intermediate_size"]
    w_in = jnp.concatenate([lp["w_gate"], lp["w_up"]], axis=-1)
    u = _proj(x, w_in, ad["mlp_in.a"], ad["mlp_in.b"], scale, precision)
    u = jax.nn.silu(u[..., :f]) * u[..., f:]
    return _proj(u, lp["w_down"], ad["mlp_out.a"], ad["mlp_out.b"], scale,
                 precision)


def _hidden(params, tokens, precision, shared: Base):
    """``tokens [B, T]`` -> the final norm's output ``[B, T, D]``, in the
    adapters' dtype."""
    m, base = shared.m, shared.arrays
    dt = params["r0.mlp_in.a"].dtype
    scale = m["lora_alpha"] / m["lora_rank"]
    mult, eps = m["residual_multiplier"], m["rms_norm_eps"]
    x = base["embed"][tokens].astype(dt) * m["embedding_multiplier"]
    for i, (kind, _) in enumerate(_runs(m)):
        mixer = _mamba if kind == MAMBA else _attention
        prefix = f"r{i}."
        ad = {k[len(prefix):]: v for k, v in params.items()
              if k.startswith(prefix)}

        def layer(x, lp_ad, mixer=mixer):
            lp, ad_l = lp_ad
            x = x + mult * mixer(m, lp, ad_l, _rms(x, lp["ln1"], eps), scale,
                                 precision)
            x = x + mult * _mlp(m, lp, ad_l, _rms(x, lp["ln2"], eps), scale,
                                precision)
            return x, None

        x, _ = jax.lax.scan(jax.checkpoint(layer), x, (base["runs"][i], ad))
    return _rms(x, base["final_norm"], eps)


def _target_logp(params, tokens, labels, precision, shared: Base):
    """``[B, T]`` float32 log-probability of each position's label (of
    label 0 where there is none), made :data:`m["logits_chunk"]`
    positions at a time."""
    m = shared.m
    h = _hidden(params, tokens, precision, shared)
    emb = shared.arrays["embed"].astype(h.dtype)
    b_, t, d = h.shape
    c = min(m["logits_chunk"], t)
    pad = (-t) % c
    h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
    lab = jnp.pad(jnp.maximum(labels, 0), ((0, 0), (0, pad)))

    def chunk(xs):
        hc, lc = xs
        logits = bilinear(_mm, hc, emb.T, precision).astype(jnp.float32)
        logits = logits / m["logits_scaling"]
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return gold - jax.nn.logsumexp(logits, axis=-1)

    n = (t + pad) // c
    out = jax.lax.map(jax.checkpoint(chunk),
                      (h.reshape(b_, n, c, d).swapaxes(0, 1),
                       lab.reshape(b_, n, c).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(b_, n * c)[:, :t]


def forward(params, tokens, precision=HIGHEST, *, shared: Base):
    """``tokens [B, T]`` -> logits ``[B, T, vocab]``, float32 (whole: for
    small sizes only)."""
    h = _hidden(params, tokens, precision, shared)
    logits = bilinear(_mm, h, shared.arrays["embed"].astype(h.dtype).T,
                      precision)
    return logits.astype(jnp.float32) / shared.m["logits_scaling"]


def loss(params, tokens, labels, precision=HIGHEST, *, shared: Base):
    """Mean next-token cross-entropy over the positions with a target."""
    logp = _target_logp(params, tokens, labels, precision, shared)
    tgt = labels >= 0
    return -jnp.sum(jnp.where(tgt, logp, 0)) / jnp.maximum(jnp.sum(tgt), 1)


def row_scores(params, tokens, labels, precision=HIGHEST, *, shared: Base):
    """``[B]``: each row's mean probability of its target tokens (0 for a
    row with none).  Rows run :data:`ROW_GROUP` at a time, and a group
    with no target is not computed (the reference pads its test rows to
    whole blocks with such rows)."""
    b_ = tokens.shape[0]
    g = min(ROW_GROUP, b_)
    pad = (-b_) % g
    tok = jnp.pad(tokens, ((0, pad), (0, 0)))
    lab = jnp.pad(labels, ((0, pad), (0, 0)), constant_values=-1)

    def group(xs):
        tg, lg = xs
        tgt = lg >= 0

        def score(_):
            p = jnp.exp(_target_logp(params, tg, lg, precision, shared))
            return (jnp.sum(jnp.where(tgt, p, 0), axis=-1)
                    / jnp.maximum(jnp.sum(tgt, axis=-1), 1))

        return jax.lax.cond(jnp.any(tgt), score,
                            lambda _: jnp.zeros(tg.shape[0], jnp.float32),
                            None)

    n = (b_ + pad) // g
    out = jax.lax.map(group, (tok.reshape(n, g, -1), lab.reshape(n, g, -1)))
    return out.reshape(-1)[:b_]


# -- operation counts ----------------------------------------------------------
def _row_flops(m: dict, t: int):
    """``(by weights, between activations)``: the forward operations of
    one row of ``t`` tokens in products with a weight (every projection
    with its adapter, the tied logits at every position) and in products
    of two activations (the SSD's chunked form at ``mamba_chunk_size``:
    ``C B^T`` once per group, the intra-chunk product, the chunk states
    and their read-out; causal attention, half of ``T x T``), two per
    multiply-add.  Norms, the convolution and elementwise work are left
    out."""
    z = _dims(m)
    r = m["lora_rank"]
    weights, mixing = 2 * z["d"] * z["v"], 0
    for kind, n in _runs(m):
        weights += n * sum(2 * (fi * fo + r * (fi + fo))
                           for fi, fo in _targets(m, kind).values())
        if kind == MAMBA:
            c = min(m["mamba_chunk_size"], t)
            mixing += n * (2 * c * z["n"] + z["h"] * (2 * c * z["p"]
                                                      + 4 * z["p"] * z["n"]))
        else:
            mixing += n * 2 * (t + 1) * z["q"]
    return t * weights, t * mixing


def forward_flops(m: dict, seq: Optional[int] = None) -> int:
    """Operations of one row's forward pass of ``seq`` tokens
    (:func:`_row_flops`); by default a test row's, ``test_seq``, which is
    what ``round_mfu`` counts for each test row an evaluation runs
    (training rows are counted by :func:`train_flops`)."""
    return sum(_row_flops(m, m["test_seq"] if seq is None else seq))


def train_flops(m: dict) -> int:
    """One training row: the forward pass; the gradient of every
    activation (as much again for a product with a weight, twice as
    much for a product of two activations, whose both inputs need one;
    the embedding needs none); and the adapters' weight gradients.  The
    frozen base has no weight gradients, and recomputation is not
    counted."""
    t, r = m["seq"], m["lora_rank"]
    weights, mixing = _row_flops(m, t)
    adapters = sum(2 * r * (fi + fo) * n for kind, n in _runs(m)
                   for fi, fo in _targets(m, kind).values())
    return 2 * weights + 3 * mixing + t * adapters


# -- the system under test -----------------------------------------------------
def _arch(m: dict):
    from repro.models.mixed import config_from_hf

    return config_from_hf(m, name="granite-4.0-h-micro")


def program_loss(params, tokens, labels, *, shared: Base):
    from repro.models import mixed

    m = shared.m
    return mixed.loss(shared.arrays, params, _arch(m), tokens, labels,
                      m["lora_alpha"], m["logits_chunk"])


def program_accuracy(params, tokens, labels, mask, *, shared: Base):
    """A masked mean of the per-row scores, as ``FederatedSimulation``
    asks of ``acc_fn``."""
    from repro.models import mixed

    m = shared.m
    s = mixed.row_scores(shared.arrays, params, _arch(m), tokens, labels,
                         m["lora_alpha"], m["logits_chunk"])
    w = mask.astype(jnp.float32)
    return jnp.sum(s * w) / jnp.maximum(jnp.sum(w), 1.0)


def program_fns():
    """The system under test's loss and accuracy; both take the base as
    ``shared=``."""
    return program_loss, program_accuracy


def make_sim(fds, params0, shared: Base, sim_cfg):
    """The ``FederatedSimulation`` with the base as an argument of its
    round block, evaluating ``eval_block`` test rows at a time."""
    from repro.federated import FederatedSimulation

    cfg = dataclasses.replace(sim_cfg, eval_block=shared.m["eval_block"])
    return FederatedSimulation(fds, params0, *program_fns(), cfg,
                               shared=shared)
