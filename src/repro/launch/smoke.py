"""Main-path phases of ``chip_smoke.py``, and the comparisons that judge them.

Each phase drives the node through its public entry points
(``repro.api`` specs + ``NodeClient``, ``AutoDFL`` + ``Scheduler``) and
returns what its reference comparison needs.  The comparisons live here
too, beside the phases, so a CPU test runs the same code at a tiny size:

  * ``run_ledger`` + ``ledger_fingerprint``: one scenario twice, once with
    the kernel factory's own choice and once ``forced_impl("numpy")``;
    the fingerprints (state root, per-window roots, batch digests, gas
    log, blocks, event stream) must be equal bit for bit.
  * ``run_fl`` + ``agg_errors``: the node's Eq. 1 merge and the Eq. 4
    distances on each task's final-round inputs against their
    ``kernels/ref.py`` oracles, plus the Fig. 3 ordering
    (``malicious_lowest``).
"""
from __future__ import annotations

import contextlib
import hashlib
import os
from typing import Dict, List, Sequence

import numpy as np

#: the paper's trainer profiles (Fig. 3), repeated to the cohort size
BEHAVIORS = ("good", "good", "malicious", "lazy")

#: Eq. 1 and Eq. 4 tolerances against ref.py.  The oracle's f32 matmul
#: may take single-pass bf16 on the TPU (the node's merge runs at full
#: f32), so Eq. 1 is held to bf16 rounding of its inputs (2^-8 of the
#: largest merged weight); Eq. 4 sums in f32 on both sides.
AGG_REL_TOL = 1e-2
DIST_REL_TOL = 1e-4


@contextlib.contextmanager
def forced_impl(impl: str):
    """Run the block with every ``"auto"`` kernel choice forced to
    ``impl`` (the factory's ``REPRO_KERNEL_IMPL``), then restore."""
    from repro.kernels.factory import IMPL_ENV
    old = os.environ.get(IMPL_ENV)
    os.environ[IMPL_ENV] = impl
    try:
        yield
    finally:
        if old is None:
            del os.environ[IMPL_ENV]
        else:
            os.environ[IMPL_ENV] = old


# -- ledger phase ---------------------------------------------------------------
def run_ledger(rate: float, duration: float, n_senders: int, seed: int = 0,
               window: float = 1.0):
    """The Table-I ``mixed`` workload through the default ``NodeSpec``
    (vector L1 + ``VectorRollup`` + prover) and the fused window loop,
    one seal per window, until every receipt is finalized.  Returns the
    ``NodeClient``."""
    from repro.api import NodeClient, NodeSpec, TxReceipt
    from repro.core.engine import TxArrays
    from repro.core.fused import FusedWindowLoop
    from repro.core.workloads import make_workload

    client = NodeClient.from_spec(NodeSpec())
    rollup = client.target
    txs = make_workload("mixed", rate, duration=duration, seed=seed,
                        n_senders=n_senders).txs
    loop = FusedWindowLoop(client.chain, rollup)
    edges = np.searchsorted(txs.submit_time,
                            np.arange(window, duration + window, window))
    seqs, lo = [], 0
    for w, hi in enumerate(edges):
        if hi > lo:
            batch = TxArrays(txs.submit_time[lo:hi], txs.gas[lo:hi],
                             txs.fn_id[lo:hi], txs.sender_id[lo:hi], txs.fns)
            seqs.append(loop.submit(rollup, batch))
        lo = hi
        t_end = (w + 1) * window
        loop.seal()
        loop.pump(t_end)
        loop.run_until(t_end)
    loop.flush()
    t_end = duration + window
    loop.run_until(t_end)
    loop.execute()
    while client.chain.n_confirmed < client.chain.n_submitted:
        t_end += window
        client.run_until(t_end)
    receipts = [client.refresh(TxReceipt("", "", 0, 0.0, seq=s))
                for a, b in seqs for s in range(a, b)]
    if len(receipts) != len(txs):
        raise AssertionError(f"{len(receipts)} receipts for {len(txs)} txs")
    pending = [r.seq for r in receipts if r.status != "finalized"]
    if pending:
        raise AssertionError(f"{len(pending)} receipts not finalized, "
                             f"first seq {pending[0]}")
    return client


def _sha(items) -> str:
    h = hashlib.sha256()
    for x in items:
        h.update(repr(x).encode())
    return h.hexdigest()[:16]


def ledger_fingerprint(client) -> Dict[str, object]:
    """Everything an impl swap must leave bit-identical, compacted to
    short hashes: the state root, every window's root, the per-batch
    digests, the gas log, the L1 blocks and the typed event stream — plus
    the final state's word buffer folded by the factory's
    ``rollup_digest`` op, so that op runs under the impl being judged."""
    from repro.kernels.factory import get_kernel
    ru, chain = client.target, client.chain
    events = client.events(cursor=0)
    words = client._state_arrays().word_buffer()
    return {
        "state_root": client.state_root(),
        "state_words_digest": int(get_kernel("rollup_digest")(words)),
        "window_roots": _sha((e.state_root, e.fabric_root, e.shard_roots)
                             for e in events if e.kind == "window_settled"),
        "batch_digests": _sha(ru.batch_digests),
        "update_digest": ru.update_digest,
        "gas_log": _sha(sorted(r.items()) for r in ru.gas_log),
        "total_gas": chain.total_gas,
        "blocks": _sha(chain.blocks),
        "events": _sha(events),
        "n_events": len(events),
        "n_batches": ru.n_batches,
    }


def fingerprint_diff(a: Dict[str, object], b: Dict[str, object]
                     ) -> List[str]:
    """Keys on which two fingerprints disagree (empty when equal)."""
    return sorted(k for k in set(a) | set(b) if a.get(k) != b.get(k))


# -- FL phase -------------------------------------------------------------------
def run_fl(model, opt, eval_fn, val, raw_batch_fn, *, n_trainers: int,
           n_tasks: int, rounds: int, local_steps: int = 2,
           spec=None, seal_every: int = 1):
    """``n_tasks`` concurrent tasks of ``VectorCohort``s (the Fig. 3
    profiles, repeated to ``n_trainers``) through ``Scheduler``.  Returns
    ``(node, scheduler, results)``."""
    from repro.api import FLTaskSpec, NodeSpec
    from repro.fl.cohort import CohortKernels, VectorCohort, batched_batch_fn
    from repro.fl.dp import DPConfig
    from repro.fl.scheduler import Scheduler
    from repro.fl.server import AutoDFL

    spec = spec or NodeSpec(trainer_funds=50.0)
    behaviors = [BEHAVIORS[i % len(BEHAVIORS)] for i in range(n_trainers)]
    dp = DPConfig(noise_multiplier=0.05)
    node = AutoDFL(model, opt, n_trainers, eval_fn, val, spec=spec)
    kern = CohortKernels(model, opt, dp)
    vbf = batched_batch_fn(raw_batch_fn, local_steps)
    sch = Scheduler(node, seal_every=seal_every)
    for t in range(n_tasks):
        cohort = VectorCohort(model, opt, vbf, node.store,
                              behaviors=behaviors, local_steps=local_steps,
                              dp=dp, seed=t, kernels=kern)
        sch.add_task(FLTaskSpec(f"task{t}", rounds=rounds, init_seed=t),
                     cohort)
    return node, sch, sch.run()


def agg_errors(sch) -> List[Dict[str, float]]:
    """Per task, on its final round's submissions and DON scores: the
    node's merged model (the scheduler's XLA merge) and
    ``core.reputation.model_distances`` (the Eq. 4 pass settlement runs)
    against their ``kernels/ref.py`` oracles.  Errors are relative to the
    oracle's largest magnitude; ``ok`` applies the module tolerances."""
    import jax.numpy as jnp

    from repro.core.aggregation import tree_flat, tree_flat_stacked
    from repro.core.reputation import model_distances
    from repro.kernels import ref

    out = []
    for rt in sch.runtimes:
        flat = tree_flat_stacked(rt.last_subs.stacked)
        scores = jnp.asarray(rt.last_scores, jnp.float32)
        merged = tree_flat(rt.params)
        want = np.asarray(ref.weighted_agg_ref(flat, scores), np.float64)
        scale = max(float(np.abs(want).max()), 1e-12)
        dwant = np.asarray(ref.model_distance_ref(flat, merged), np.float64)
        dgot = np.asarray(model_distances(flat, merged), np.float64)
        e = {"task": rt.task_id, "n": int(flat.shape[0]),
             "P": int(flat.shape[1]),
             "agg_node": float(np.abs(np.asarray(merged, np.float64)
                                      - want).max()) / scale,
             "distance": float(np.abs(dgot - dwant).max()
                               / max(float(np.abs(dwant).max()), 1e-12))}
        e["ok"] = (e["agg_node"] <= AGG_REL_TOL
                   and e["distance"] <= DIST_REL_TOL)
        out.append(e)
    return out


def malicious_lowest(node, behaviors: Sequence[str] = BEHAVIORS) -> bool:
    """Fig. 3: a malicious trainer holds the lowest reputation, and every
    malicious trainer ends below every good one.  A lazy trainer may tie
    the lowest: skipping a task's final round scores it like the farthest
    submission (Eq. 2 objective reputation 0), as a malicious one is."""
    rep = np.asarray(node.book.reputation, np.float64)
    kind = np.array([behaviors[i % len(behaviors)]
                     for i in range(rep.shape[0])])
    bad, good = rep[kind == "malicious"], rep[kind == "good"]
    return bool(bad.size and good.size and bad.min() <= rep.min()
                and bad.max() < good.min())
