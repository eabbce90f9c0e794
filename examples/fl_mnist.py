"""The paper's own end-to-end workload (§VI): LeNet-5 federated training with
good / malicious / lazy trainers, DON evaluation, reputation-weighted
aggregation (Eq. 1), zk-rollup settlement, escrow payouts.

This is the Fig. 3 experiment as a runnable script.

Usage:
    PYTHONPATH=src python examples/fl_mnist.py --tasks 5 --rounds 4
"""
import argparse

import jax
import jax.numpy as jnp

from repro.api import ChainSpec, FLTaskSpec, NodeSpec, RollupSpec
from repro.configs.registry import get_config
from repro.data.pipeline import client_batch_fn
from repro.data.synthetic import make_mnist_like
from repro.fl.client import ClientConfig, TrainingAgent
from repro.fl.dp import DPConfig
from repro.fl.partition import dirichlet_partition, skew_report
from repro.fl.server import AutoDFL
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lenet
from repro.models.model import build_model
from repro.optim.optimizers import OptimizerSpec, make_optimizer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tasks", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--no-rollup", action="store_true",
                    help="single-layer L1 baseline (paper Fig. 5 comparison)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config("lenet5")
    model = build_model(cfg)
    opt = make_optimizer(OptimizerSpec(name="sgdm", lr=0.05, grad_clip=5.0))

    xs, ys = make_mnist_like(2048, seed=1)
    val = {"images": jnp.asarray(xs[:256]), "labels": jnp.asarray(ys[:256])}
    parts = dirichlet_partition(ys[256:], args.clients, alpha=0.8, seed=0)
    print("non-IID partition:", skew_report(ys[256:], parts)["sizes"])
    raw = client_batch_fn(xs[256:], ys[256:], parts, 64)
    bf = lambda c, r: {k: jnp.asarray(v) for k, v in raw(c, r).items()}
    eval_fn = jax.jit(lambda p, b: lenet.accuracy(cfg, p, b))

    # public API: the node is described by a spec — the paper-faithful
    # object engine, with the L2 rollup unless --no-rollup asked for the
    # single-layer baseline
    spec = NodeSpec(chain=ChainSpec(backend="object"),
                    rollup=None if args.no_rollup else RollupSpec())
    sys = AutoDFL(model, opt, args.clients, eval_fn, val, spec=spec)
    behaviors = (["good", "good", "malicious", "lazy"] * 8)[: args.clients]
    agents = [TrainingAgent(
        ClientConfig(f"trainer{i}", behaviors[i],
                     dp=DPConfig(noise_multiplier=0.05)),
        model, opt, sys.store, bf, seed=i) for i in range(args.clients)]

    print(f"{'task':>5s} | " + " | ".join(
        f"{b[:4]}{i}" for i, b in enumerate(behaviors)))
    res = None
    for t in range(args.tasks):
        res = sys.run_task(FLTaskSpec(f"task{t}", rounds=args.rounds),
                           agents, bf)
        reps = " | ".join(f"{r:5.3f}" for r in res.reputations)
        print(f"{t:5d} | {reps}")

    acc = float(eval_fn(res.global_params, val))
    print(f"\nglobal model accuracy: {acc:.3f}")
    print(f"payouts (last task): "
          f"{ {k: round(v, 2) for k, v in res.payouts.items()} }")
    if sys.rollup is not None:
        total_l2 = sum(b['total'] for b in sys.rollup.gas_log)
        print(f"rollup: {len(sys.rollup.batches)} batches, "
              f"settled gas={total_l2:.0f}")
    print(f"L1 chain: {len(sys.chain.blocks)} blocks, "
          f"gas={sys.chain.total_gas:.0f}")


if __name__ == "__main__":
    main()
