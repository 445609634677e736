"""Sequential mixed decoder: each layer's mixer is named by ``layer_types``.

IBM's Granite-4.0-H models (``model_type: granitemoehybrid``) stack
Mamba-2 and attention layers one after the other, and every layer
carries a gated MLP after its mixer:

    h  = x + m_r * mixer(norm(x))        mixer: Mamba-2 or NoPE GQA
    x' = h + m_r * mlp(norm(h))

with the token embeddings times ``embedding_multiplier``, a final norm,
and logits tied to the embedding and divided by ``logits_scaling``.
Unlike ``transformer.py``'s ``block_type="hybrid"`` (Hymba's parallel
branches in every layer), a layer here runs one mixer.  The mixers and
the MLP are the model zoo's own: :func:`ssm.ssm_apply` (the chunked SSD
scan), :func:`attention.attention_apply` (no rotary angles, score scale
``attn_scale``) and :func:`layers.gated_mlp`.

Consecutive layers of one kind form a *run*; each run's layers are
stacked and scanned, each layer rematerialised.  The loss runs the tied
unembedding in position chunks (:func:`transformer.chunked_ce_loss`),
so the ``[tokens, vocab]`` logits are never whole.

For federated fine-tuning the weights come in two parts: a frozen
*base* every client holds, and low-rank *adapters* (arXiv:2106.09685),
the only weights clients train.  Every adapted projection computes
``x @ W + (alpha / r) (x @ A) @ B`` (:class:`layers.LoRA`): ``in_proj``
and ``out_proj`` of each Mamba-2 mixer, ``wq``/``wk``/``wv``/``wo``,
and the MLP's fused gate-and-up projection (``mlp_in``, one adapter
over ``[w_gate | w_up]``, as the checkpoint's ``input_linear``) and its
down projection (``mlp_out``).  Adapters are a flat dict
``{"r<run>.<target>.<a|b>": [layers of the run, in, r] / [.., r, out]}``.

Parts of the model open ``model.*`` scopes (:func:`spans.part`):
``mamba`` (the mixer less its scan, which ``ssm_apply`` names ``ssd``),
``attention``, ``mlp`` and ``logits``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.models.attention import attention_apply
from repro.models.config import ArchConfig
from repro.models.layers import LoRA, gated_mlp, rmsnorm
from repro.models.ssm import ssm_apply
from repro.models.transformer import chunked_ce_loss
from repro.utils import spans

Params = Dict[str, jax.Array]
MAMBA, ATTENTION = "mamba", "attention"
#: the frozen leaves of a Mamba-2 mixer, in ``ssm_apply``'s names
SSM_KEYS = ("in_proj", "conv_w", "conv_b", "A_log", "D", "dt_bias",
            "norm_scale", "out_proj")
ATTN_KEYS = ("wq", "wk", "wv", "wo")


def config_from_hf(hf: dict, name: str = "granitemoehybrid") -> ArchConfig:
    """The :class:`ArchConfig` of a ``granitemoehybrid`` ``config.json``
    (its keys, as published) without experts: the parts this module
    implements, refused where the config asks for another."""
    if hf.get("num_local_experts", 0):
        raise ValueError("sparse experts are not implemented here")
    if hf.get("position_embedding_type", "nope") != "nope":
        raise ValueError("only NoPE attention is implemented here")
    if hf.get("mamba_n_groups", 1) != 1:
        raise ValueError("ssm_apply has one group of B and C")
    if hf.get("mamba_proj_bias") or hf.get("attention_bias"):
        raise ValueError("projection biases are not implemented here")
    if not hf.get("mamba_conv_bias", True):
        raise ValueError("ssm_apply's convolution has a bias")
    d = hf["hidden_size"]
    heads = hf["num_attention_heads"]
    d_inner = hf["mamba_expand"] * d
    if hf["mamba_n_heads"] * hf["mamba_d_head"] != d_inner:
        raise ValueError("mamba_n_heads * mamba_d_head != mamba_expand * "
                         "hidden_size")
    kinds = tuple(hf["layer_types"])
    if len(kinds) != hf["num_hidden_layers"] or \
            not set(kinds) <= {MAMBA, ATTENTION}:
        raise ValueError(f"layer_types {kinds} do not give "
                         f"{hf['num_hidden_layers']} mamba/attention layers")
    return ArchConfig(
        name=name, arch_type="hybrid", num_layers=len(kinds), d_model=d,
        d_ff=hf["shared_intermediate_size"], vocab_size=hf["vocab_size"],
        num_heads=heads, num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim") or d // heads,
        ssm_state=hf["mamba_d_state"], ssm_expand=hf["mamba_expand"],
        ssm_headdim=hf["mamba_d_head"], ssm_chunk=hf["mamba_chunk_size"],
        ssm_conv=hf["mamba_d_conv"], block_type="mixed", layer_types=kinds,
        attn_scale=hf["attention_multiplier"], act=hf["hidden_act"],
        norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        embedding_multiplier=hf["embedding_multiplier"],
        residual_multiplier=hf["residual_multiplier"],
        logits_scaling=hf["logits_scaling"], dtype="bfloat16")


def runs(cfg: ArchConfig) -> List[Tuple[str, int]]:
    """``[(kind, layers)]``: the stack's consecutive layers of one kind."""
    out: List[Tuple[str, int]] = []
    for kind in cfg.layer_types:
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1] + 1)
        else:
            out.append((kind, 1))
    return out


def _adapters_of(adapters: Params, run: int) -> Params:
    prefix = f"r{run}."
    return {k[len(prefix):]: v for k, v in adapters.items()
            if k.startswith(prefix)}


def _lora(w, ad: Params, target: str, scale: float, cols=None) -> LoRA:
    a, b = ad[target + ".a"], ad[target + ".b"]
    if cols is not None:
        b = b[:, cols]
    return LoRA(w, a, b, scale)


def _mlp(cfg: ArchConfig, lp: Params, ad: Params, x, scale: float):
    with spans.part("mlp"):
        h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
        f = cfg.d_ff
        p = {"w_gate": _lora(lp["w_gate"], ad, "mlp_in", scale, slice(0, f)),
             "w_up": _lora(lp["w_up"], ad, "mlp_in", scale, slice(f, None)),
             "w_down": _lora(lp["w_down"], ad, "mlp_out", scale)}
        return x + cfg.residual_multiplier * gated_mlp(p, h, cfg.act)


def _mamba_layer(cfg: ArchConfig, lp: Params, ad: Params, x, scale: float):
    with spans.part("mamba"):
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        p = {k: lp[k] for k in SSM_KEYS}
        for target in ("in_proj", "out_proj"):
            p[target] = _lora(lp[target], ad, target, scale)
        out, _ = ssm_apply(p, cfg, h)
        x = x + cfg.residual_multiplier * out
    return _mlp(cfg, lp, ad, x, scale)


def _attention_layer(cfg: ArchConfig, lp: Params, ad: Params, x,
                     scale: float):
    with spans.part("attention"):
        h = rmsnorm(x, lp["ln1"], cfg.norm_eps)
        p = {k: _lora(lp[k], ad, k, scale) for k in ATTN_KEYS}
        out, _ = attention_apply(p, cfg, h, None, causal=True)
        x = x + cfg.residual_multiplier * out
    return _mlp(cfg, lp, ad, x, scale)


def hidden(base: dict, adapters: Params, cfg: ArchConfig, tokens,
           lora_alpha: float):
    """``tokens [B, T]`` -> the final norm's output ``[B, T, D]``, in the
    adapters' dtype."""
    dt = next(iter(adapters.values())).dtype
    x = jnp.take(base["embed"], tokens, axis=0).astype(dt)
    x = x * cfg.embedding_multiplier
    for i, (kind, _) in enumerate(runs(cfg)):
        ad = _adapters_of(adapters, i)
        rank = ad["mlp_in.a"].shape[-1]
        layer = _mamba_layer if kind == MAMBA else _attention_layer

        def body(x, xs, layer=layer, scale=lora_alpha / rank):
            lp, ad_l = xs
            return layer(cfg, lp, ad_l, x, scale), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, (base["runs"][i], ad))
    return rmsnorm(x, base["final_norm"], cfg.norm_eps)


def loss(base: dict, adapters: Params, cfg: ArchConfig, tokens, labels,
         lora_alpha: float, chunk: int = 256):
    """Mean next-token cross-entropy over the positions with a target
    (label ``>= 0``)."""
    h = hidden(base, adapters, cfg, tokens, lora_alpha)
    with spans.part("logits"):
        tgt = (labels >= 0).astype(jnp.float32)
        return chunked_ce_loss(cfg, {"embed": base["embed"]},
                               h / cfg.logits_scaling,
                               jnp.maximum(labels, 0), tgt, chunk)


def row_scores(base: dict, adapters: Params, cfg: ArchConfig, tokens,
               labels, lora_alpha: float, chunk: int = 256):
    """``[B]``: each row's mean probability of its target tokens, over
    its positions with a target (0 where it has none).  Logits are made
    ``chunk`` positions at a time."""
    h = hidden(base, adapters, cfg, tokens, lora_alpha) / cfg.logits_scaling
    with spans.part("logits"):
        b, t, d = h.shape
        c = min(chunk, t)
        pad = (-t) % c
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        lab = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
        w = base["embed"].T

        def body(_, xs):
            hh, ll = xs                              # [B, c, D], [B, c]
            logits = (hh @ w.astype(hh.dtype)).astype(jnp.float32)
            gold = jnp.take_along_axis(logits, jnp.maximum(ll, 0)[..., None],
                                       axis=-1)[..., 0]
            p = jnp.exp(gold - jax.nn.logsumexp(logits, axis=-1))
            return None, jnp.sum(jnp.where(ll >= 0, p, 0.0), axis=-1)

        n = (t + pad) // c
        xs = (h.reshape(b, n, c, d).swapaxes(0, 1),
              lab.reshape(b, n, c).swapaxes(0, 1))
        _, hits = jax.lax.scan(body, None, xs)
        count = jnp.sum(labels >= 0, axis=-1)
        return jnp.sum(hits, axis=0) / jnp.maximum(count, 1)
