"""Serving driver: batched prefill + greedy decode with per-family caches.

Demonstrates the full inference path (prefill builds the KV/SSM cache,
decode extends it token by token) on whatever devices exist.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import jitcache
from repro.models import model as model_lib
from repro.train import steps


def generate(cfg, params, prompts: jax.Array, max_new: int,
             enc_frames=None) -> tuple[np.ndarray, dict]:
    """prompts (B, S_prompt) int32 -> (B, S_prompt + max_new) tokens."""
    B, S = prompts.shape
    horizon = S + max_new
    pf_kwargs = {}
    if cfg.mrope_sections is not None:
        pf_kwargs["mrope_pos"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, None], (3, B, S))
    if cfg.family == "encdec":
        pf_kwargs["enc_frames"] = enc_frames

    t0 = time.time()
    logits, cache = jax.jit(
        lambda p, t: model_lib.prefill(p, cfg, t, **pf_kwargs))(params, prompts)
    cache = model_lib.extend_cache(cache, horizon)
    t_prefill = time.time() - t0

    serve_step = jax.jit(steps.build_serve_step(cfg))
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    out = [token]
    t0 = time.time()
    for i in range(max_new - 1):
        token, _, cache = serve_step(params, cache, token,
                                     jnp.int32(S + i))
        out.append(token)
    jax.block_until_ready(token)
    t_decode = time.time() - t0
    gen = np.concatenate([np.asarray(prompts)] + [np.asarray(t) for t in out],
                         axis=1)
    stats = {"prefill_s": t_prefill, "decode_s": t_decode,
             "decode_tok_per_s": B * (max_new - 1) / max(t_decode, 1e-9)}
    return gen, stats


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    args = ap.parse_args()
    jitcache.enable_persistent_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    params = model_lib.init(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab, dtype=jnp.int32)
    enc = None
    if cfg.family == "encdec":
        enc = jax.random.normal(key, (args.batch, cfg.enc_ctx, cfg.d_model),
                                jnp.bfloat16)
    gen, stats = generate(cfg, params, prompts, args.max_new, enc_frames=enc)
    print(f"generated {gen.shape} tokens; prefill {stats['prefill_s']:.2f}s, "
          f"decode {stats['decode_tok_per_s']:.1f} tok/s")


if __name__ == "__main__":
    main()
