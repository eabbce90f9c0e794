"""Quickstart: the AutoDFL reproduction in ~80 lines.

1. Drive the public node API: NodeSpec -> NodeClient -> tx receipts,
   account views, state root (the zk-rollup RPC surface).
2. Build any assigned architecture from the registry (--arch).
3. Run a few training steps on CPU with a reduced config.
4. Run one reputation-weighted rollup round (the paper's technique).

Usage:
    PYTHONPATH=src python examples/quickstart.py --arch qwen2-0.5b --steps 3
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import NodeClient, NodeSpec, ShardSpec
from repro.configs.registry import REGISTRY, reduced_config
from repro.fl.round import FLRoundSpec, build_fl_round
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.optim.optimizers import OptimizerSpec, make_optimizer


def api_demo():
    """The public API path: typed spec -> client -> receipts + events."""
    spec = NodeSpec(shards=ShardSpec(count=2))    # 2-shard L2 over one L1
    client = NodeClient.from_spec(spec)
    receipts = [client.submit("submitLocalModel", f"trainer{i % 4}")
                for i in range(25)]
    client.flush()                                 # seal + prove + settle
    client.run_until(5.0)                          # L1 blocks to t=5s
    r = client.refresh(receipts[0])
    print(f"tx receipt: status={r.status} shard={r.shard} batch={r.batch} "
          f"aggregate={r.aggregate_ref} l1_block={r.block} "
          f"gas={r.gas_breakdown['batch_total']:.0f} "
          f"verify_share={r.gas_breakdown['verify_share']:.1f}")
    acct = client.get_account("trainer0")
    print(f"account trainer0: submissions={acct.submissions} "
          f"reputation={acct.reputation:.2f}")
    events = client.events()                       # typed, pull-based
    kinds = sorted({e.kind for e in events})
    windows = [e for e in events if e.kind == "window_settled"]
    print(f"state root: {client.state_root()}  "
          f"(events: {kinds}, windows: {len(windows)})")
    assert r.status == "finalized" and acct.submissions > 0 and windows
    assert windows[-1].fabric_root
    assert "block_packed" in client.capabilities()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=sorted(REGISTRY))
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()
    enable_compile_cache()

    api_demo()

    cfg = reduced_config(REGISTRY[args.arch])
    print(f"arch={cfg.name} family={cfg.family} (reduced config for CPU)")
    model = build_model(cfg)
    opt = make_optimizer(OptimizerSpec(name="sgdm", lr=0.05))
    params = model.init_params(jax.random.key(0))
    state = opt.init(params)

    rng = np.random.default_rng(0)
    B, S = 2, 16

    def batch(seed):
        toks = rng.integers(0, cfg.vocab_size, (B, S + 1))
        b = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
             "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
        if cfg.input_mode == "embeds":
            b = {"embeds": jnp.asarray(
                     rng.normal(0, 0.02, (B, S, cfg.d_model)), jnp.bfloat16),
                 "positions": jnp.broadcast_to(
                     jnp.arange(S, dtype=jnp.int32), (3, B, S)),
                 "labels": b["labels"]}
        elif cfg.input_mode == "audio":
            b["audio_embeds"] = jnp.asarray(
                rng.normal(0, 0.02, (B, cfg.enc_seq, cfg.d_model)),
                jnp.bfloat16)
        elif cfg.family == "conv":
            b = {"images": jnp.asarray(rng.normal(size=(B, 32, 32, 1)),
                                       jnp.float32),
                 "labels": jnp.zeros((B,), jnp.int32)}
        return b

    @jax.jit
    def step(p, o, b):
        loss, g = jax.value_and_grad(lambda pp: model.loss(pp, b))(p)
        p, o, _ = opt.update(g, o, p)
        return p, o, loss

    for i in range(args.steps):
        params, state, loss = step(params, state, batch(i))
        print(f"step {i}: loss={float(loss):.4f}")

    if cfg.family != "conv" and cfg.input_mode == "tokens":
        # one rollup round with 2 virtual trainers (the paper's technique)
        T, H = 2, 2
        fl_round = build_fl_round(model, opt, FLRoundSpec(T, H, B))
        params_T = jax.tree.map(lambda l: jnp.stack([l] * T), params)
        opt_T = jax.tree.map(lambda l: jnp.stack([l] * T), state)
        toks = rng.integers(0, cfg.vocab_size, (T, H, B, S + 1))
        batches = {"tokens": jnp.asarray(toks[..., :-1], jnp.int32),
                   "labels": jnp.asarray(toks[..., 1:], jnp.int32)}
        scores = jnp.array([0.9, 0.6])
        params_T, opt_T, m = jax.jit(fl_round)(params_T, opt_T, scores,
                                               batches)
        print(f"rollup round: loss={float(m['loss']):.4f} "
              f"distances={np.asarray(m['distances']).round(3)} "
              f"digest=0x{int(m['digest']):08x}")
    print("done.")


if __name__ == "__main__":
    main()
