"""LM serving demo: batched prefill + decode loop.

    PYTHONPATH=src python -m repro.launch.serve_lm --arch qwen2-7b --batch 4 \
        --prompt-len 32 --gen 16 [--smoke]

Greedy decode with the ring-buffer KV cache (or recurrent state for
SSM/hybrid archs). On CPU use --smoke. The social-prediction serving
front end lives in `repro.launch.serve`.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch import steps
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model


def serve(arch: str, *, batch: int = 4, prompt_len: int = 32, gen: int = 16,
          cache_len: int = 128, smoke: bool = True, seed: int = 0) -> dict:
    cfg = get_config(arch)
    if smoke:
        cfg = cfg.reduced()
    model = build_model(cfg)
    # independent randomness for params, prompts and priming frames —
    # reusing one key would correlate the weights with the inputs
    init_key, prompt_key, prime_key = jax.random.split(
        jax.random.PRNGKey(seed), 3)
    params = model.init(init_key)
    serve_step = jax.jit(steps.make_serve_step(model), donate_argnums=(1,))

    prompts = jax.random.randint(prompt_key, (batch, prompt_len), 0,
                                 cfg.vocab_size)
    cache = model.init_cache(batch, cache_len)
    if model.prime_cache is not None:
        frames = jax.random.normal(
            prime_key, (batch, max(cache_len // 4, 8), cfg.d_model))
        cache = model.prime_cache(params, cache, frames.astype(cfg.jdtype))

    # prefill token-by-token through the decode path (fills cache + state);
    # block-prefill via apply() is benchmarked separately in benchmarks/.
    t0 = time.time()
    tok = prompts[:, :1]
    out_tokens = [tok]
    for i in range(prompt_len - 1):
        pos = jnp.full((batch,), i, jnp.int32)
        nxt, cache = serve_step(params, cache, tok, pos)
        tok = prompts[:, i + 1: i + 2]
    # generate
    for i in range(gen):
        pos = jnp.full((batch,), prompt_len - 1 + i, jnp.int32)
        nxt, cache = serve_step(params, cache, tok, pos)
        tok = nxt[:, None]
        out_tokens.append(tok)
    jax.block_until_ready(tok)          # honest wall clock: wait for compute
    dt = time.time() - t0
    toks = np.asarray(jnp.concatenate(out_tokens, axis=1))
    print(f"{arch}: generated {gen} tokens x batch {batch} in {dt:.2f}s "
          f"({(prompt_len + gen - 1) / dt:.1f} steps/s)")
    print("sample token ids:", toks[0, -min(gen, 10):].tolist())
    return {"tokens": toks, "seconds": dt}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    args = ap.parse_args()
    enable_compile_cache()
    serve(args.arch, batch=args.batch, prompt_len=args.prompt_len, gen=args.gen,
          cache_len=args.cache_len, smoke=args.smoke)


if __name__ == "__main__":
    main()
