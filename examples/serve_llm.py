"""Serve the federated global model: batched prefill + decode.

Exercises the serving substrate the decode dry-run shapes lower — KV cache
(full or ring layout), batched requests, greedy decoding — on the host.

    PYTHONPATH=src python examples/serve_llm.py --requests 4 --new-tokens 16
    PYTHONPATH=src python examples/serve_llm.py --arch mamba2-2.7b  # O(1) state
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS
from repro.models.registry import bundle as make_bundle
from repro.utils.compile_cache import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=sorted(ARCHS))
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--ring", action="store_true",
                    help="ring-buffer (sliding window) KV cache")
    args = ap.parse_args()

    cfg = ARCHS[args.arch].reduced()
    if args.ring:
        cfg = cfg.with_overrides(layer_windows=(16,), long_context_window=16)
    mdl = make_bundle(cfg)
    params = mdl.init(jax.random.key(0))

    B, P, N = args.requests, args.prompt_len, args.new_tokens
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, P)), jnp.int32)
    batch = {"tokens": prompts}
    if cfg.arch_type == "audio":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.num_frontend_tokens, cfg.d_model)) * 0.02,
            cfg.param_dtype)
    layout = "ring" if args.ring else "full"

    cache = mdl.init_cache(B, P + N, layout)
    prefill = jax.jit(lambda p, b, c: mdl.prefill(p, b, c, layout=layout))
    decode = jax.jit(lambda p, t, i, c: mdl.decode_step(p, t, i, c,
                                                        layout=layout))

    t0 = time.time()
    logits, cache = prefill(params, batch, cache)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0
    print(f"[serve] {args.arch}: prefill {B}x{P} in {t_prefill*1e3:.0f}ms "
          f"(cache layout: {layout})")

    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    generated = [np.asarray(tok)[:, 0]]
    t0 = time.time()
    for step in range(N - 1):
        logits, cache = decode(params, tok, jnp.asarray(P + step, jnp.int32),
                               cache)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        generated.append(np.asarray(tok)[:, 0])
    jax.block_until_ready(tok)
    dt = time.time() - t0
    toks_s = B * (N - 1) / max(dt, 1e-9)
    print(f"[serve] decoded {N-1} steps x {B} requests in {dt*1e3:.0f}ms "
          f"({toks_s:.1f} tok/s, greedy)")
    gen = np.stack(generated, axis=1)
    for b in range(min(B, 2)):
        print(f"[serve] request {b}: {gen[b].tolist()}")


if __name__ == "__main__":
    use_compile_cache()
    main()
