"""Multi-task protocol scheduler: N concurrent FL tasks on one shared clock,
ledger and reputation book.

``AutoDFL.run_task`` (fl/server.py) used to be a monolithic loop; it is now
split into

  * ``TaskRuntime`` — the per-task state machine (paper Fig. 1 steps 1-16):
    select -> [train -> evaluate -> aggregate] x rounds -> settle.  Each
    ``step()`` advances one phase, so a scheduler can interleave many tasks
    at round granularity.
  * ``Scheduler`` — drives N TaskRuntimes on a shared window clock.  Every
    window, each active task steps once; all lifecycle/reputation
    transactions land in the node's ONE shared chain/rollup (the paper's
    congestion scenario), optionally racing a background ``Workload``
    (core/workloads.py) for block gas.  Tasks that finish in the same
    window settle TOGETHER through the fused multi-task reputation update
    (core/reputation.end_of_multitask_update) — one dispatch per window.

Single-task equivalence: a ``Scheduler`` with one task reproduces
``AutoDFL.run_task`` outputs exactly (tests/test_scheduler.py) — run_task
itself drives a TaskRuntime sequentially, and gas totals are invariant to
block/window timing.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.aggregation import (tree_flat, tree_flat_stacked,
                                    weighted_average_tree_jit,
                                    weighted_average_tree_mega)
from repro.core.oracle import (_UNBATCHABLE, _eval_cache_get,
                               _eval_cache_key, _eval_cache_put,
                               evaluate_quorum,
                               mega_score_tables, quorum_from_table)
from repro.core.reputation import model_distances
from repro.fl.cohort import (AgentCohort, CohortSubmissions, MegaCohort,
                             VectorCohort, _unstack_fn, pad_rows)

_log = logging.getLogger(__name__)
# (chain type, rollup type) pairs already warned about falling back to
# the stepped path under fused="auto" — the log fires once per stack
# shape per process, not once per run (tests reset this set directly)
_FUSED_FALLBACK_WARNED: set = set()


@jax.jit
def _settle_distances(stacked_tree, global_tree):
    """Batched Eq. 4 distance pass for the final submissions (one fused
    dispatch per task at settlement)."""
    return model_distances(tree_flat_stacked(stacked_tree),
                           tree_flat(global_tree))


def _padded(subs: CohortSubmissions, n: int):
    """The submissions as ``n`` rows (zero rows past the submitters): one
    program shape for the merge and the distances whoever skipped."""
    return subs.padded if subs.padded is not None else \
        pad_rows(subs.stacked, n)


def _padded_scores(scores, n: int) -> jnp.ndarray:
    w = np.zeros(n, np.float32)
    s = np.asarray(scores, np.float32)
    w[:s.size] = s
    return jnp.asarray(w)


@dataclasses.dataclass
class RoundRecord:
    """What one round of a task took in and gave out, as the protocol
    published it: the global model in, the submitters (cohort indices,
    ascending) and their stacked models, the DON score table (oracles x
    submitters) and its medians, and the Eq. 1 merge out.  Built only for
    a ``Scheduler(on_round=...)`` subscriber, which decides what to keep."""

    task_id: str
    params_in: Any
    idxs: List[int]
    stacked: Any
    table: np.ndarray
    scores: np.ndarray
    params_out: Any


class TaskRuntime:
    """Per-task state machine over a shared protocol node (AutoDFL).

    Phases: "select" -> "round" (x rounds) -> "settle_ready" -> "done".
    ``step()`` advances one phase; settlement is performed by the node
    (``AutoDFL.settle_window``) so that tasks closing in the same scheduler
    window share one fused reputation update.
    """

    def __init__(self, node, task_id: str, cohort, *, rounds: int = 5,
                 reward: float = 10.0, n_select: Optional[int] = None,
                 init_seed: int = 0):
        if isinstance(cohort, (list, tuple)):
            cohort = AgentCohort(cohort)
        assert len(cohort) == len(node.trainer_ids), \
            "cohort must cover the node's trainer set"
        self.node = node
        self.task_id = task_id
        self.cohort = cohort
        self.rounds = rounds
        self.reward = reward
        self.n_select = n_select
        self.init_seed = init_seed
        # sharded fabric (core/shards.py): pin every emission of this task
        # to one shard — hash or least-loaded, decided at task creation
        rollup = getattr(node, "rollup", None)
        self.shard: Optional[int] = (rollup.assign_task(task_id)
                                     if hasattr(rollup, "assign_task")
                                     else None)
        self.phase = "select"
        self.rnd = 0
        self.start_window = 0
        n = len(cohort)
        self.completed = np.zeros(n, np.float32)
        self.sel_idx: List[int] = []
        self.params = None
        self.last_subs: Optional[CohortSubmissions] = None
        self.last_scores: Optional[np.ndarray] = None
        # settlement arrays, filled by _finalize
        self.score_auto = np.zeros(n, np.float32)
        self.dists = np.zeros(n, np.float32)
        self.participated = np.zeros(n, np.float32)
        self.result = None
        # called with a RoundRecord after each round that had submitters
        self.on_round: Optional[Callable[[RoundRecord], None]] = None

    # -- lifecycle -------------------------------------------------------------
    def step(self):
        # every protocol tx emitted while this task steps is routed to the
        # task's shard (no-op when the L2 target is not a sharded fabric)
        self.node._route_shard = self.shard
        try:
            if self.phase == "select":
                self._select()
                self.phase = "round"
                if self.rounds == 0:
                    self._finalize()
            elif self.phase == "round":
                self._round()
                if self.rnd >= self.rounds:
                    self._finalize()
            else:
                raise RuntimeError(f"step() in phase {self.phase!r} "
                                   f"(task {self.task_id})")
        finally:
            self.node._route_shard = None

    # steps 1-2: publish + reputation-ranked selection --------------------------
    def _select(self):
        with obs.span("fl.select"):
            node = self.node
            model_cid = node.store.put({"arch": node.model.cfg.name})
            node.tsc.publish_task(node.publisher, self.task_id, model_cid,
                                  model_cid, self.rounds, 0.5, self.reward)
            node._tx("publishTask", node.publisher, {"taskId": self.task_id})
            # array reputations straight from the book — no dict roundtrip
            selected = node.tsc.select_trainers(
                self.task_id, np.asarray(node.book.reputation),
                self.n_select or len(self.cohort), trainer_ids=node.trainer_ids)
            self.sel_idx = [node.trainer_index(t) for t in selected]
            for t in selected:
                node.escrow.lock_collateral(t, self.task_id, 1.0)
            self.params = node.model.init_params(jax.random.key(self.init_seed))
            self.cohort.start_task(self.params, node.opt, self.sel_idx)
        obs.count("fl.tasks")

    # steps 3-15: one round (local training -> DON -> Eq. 1 merge) --------------
    def _round(self):
        node = self.node
        subs = self.cohort.train(self.params, self.rnd, self.sel_idx)
        self.rnd += 1
        obs.count("fl.rounds")
        if subs is None:
            node.tsc.advance_round(self.task_id)
            return
        senders = []
        for i in subs.idxs:
            tid = node.trainer_ids[i]
            node.tsc.submit_local_model(tid, self.task_id, self.rnd - 1,
                                        subs.cids[i])
            senders.append(tid)
        node._tx_batch("submitLocalModel", senders,
                       lambda: [{"taskId": self.task_id,
                                 "round": self.rnd - 1, "cid": subs.cids[i]}
                                for i in subs.idxs])
        self.completed[subs.idxs] += 1.0
        scores, report = evaluate_quorum(node.eval_fn, subs.stacked, None,
                                         node.don, slices=node.val_slices)
        obs.count("fl.eval_images",
                  len(subs.idxs) * node.val_slices.n_images)
        scores_np = np.asarray(scores, np.float32)
        node._tx_batch("calculateObjectiveRep", senders,
                       lambda: [{"value": float(s)} for s in scores_np])
        params_in = self.params
        k = len(self.sel_idx)
        with obs.span("fl.aggregate"):
            self.params = weighted_average_tree_jit(
                _padded(subs, k), _padded_scores(scores_np, k))
        node.tsc.advance_round(self.task_id)
        self.last_subs = subs
        self.last_scores = scores_np
        if self.on_round is not None:
            self.on_round(RoundRecord(self.task_id, params_in, subs.idxs,
                                      subs.stacked, report["table"],
                                      scores_np, self.params))

    # step 16 prep: cohort settlement arrays ------------------------------------
    def _finalize(self):
        """Distances + final scores for the end-of-task update.

        Final scores REUSE the last round's DON quorum medians instead of
        re-evaluating every final model (that double work was pure overlap
        with the round-loop quorum).  Distances are computed for submitters
        first in one batched Eq. 4 pass; every selected non-submitter then
        gets the max over SUBMITTED distances (the old in-loop fallback read
        a partially-filled array, so the penalty depended on iteration
        order)."""
        self.participated[self.sel_idx] = 1.0
        d = np.zeros(0, np.float32)
        if self.last_subs is not None:
            n = len(self.last_subs.idxs)
            with obs.span("fl.aggregate"):
                d = np.asarray(_settle_distances(
                    _padded(self.last_subs, len(self.sel_idx)),
                    self.params), np.float32)[:n]
            self.dists[self.last_subs.idxs] = d
            self.score_auto[self.last_subs.idxs] = self.last_scores
        # degenerate case (no submitters, or every submitted distance is
        # exactly 0, e.g. a single submitter whose model IS the merge):
        # keep the legacy 1.0 penalty so free-riders never score best
        fallback = float(d.max()) if d.size and float(d.max()) > 0 else 1.0
        submitted = set(self.last_subs.idxs) if self.last_subs else set()
        for i in self.sel_idx:
            if i not in submitted:
                self.dists[i] = fallback
        self.phase = "settle_ready"


class Scheduler:
    """Interleave N TaskRuntimes on a shared window clock.

    window: simulated seconds per scheduling window; every active task
    advances one phase per window and the L1 produces blocks up to the
    window edge.  ``background`` (a core/workloads.py Workload) is injected
    into the shared L1 in time order, racing protocol traffic for block gas.
    ``seal_every``: seal rollup lane batches every k windows (0 = only the
    final flush, which preserves single-task batch-boundary equivalence
    with ``run_task``).
    ``fused``: drive the ledger hot path through the core/fused.py plan-
    then-execute loop — "auto" (on when the stack supports it), True
    (assert support), or False (always Python-stepped).  Fused and stepped
    runs are pinned to identical outputs (tests/test_fused.py).
    ``megabatch``: when every task stepping in a window is in its "round"
    phase and the cohorts share one compiled kernel set, run the whole
    window as ONE cross-task megastep — a (tasks, trainers) double-vmapped
    train/score/aggregate program plus one megabatched tx emission —
    instead of T per-task dispatches.  "auto" (on when eligible), True
    (assert eligibility on all-round windows), or False (always per-task).
    Megabatched and per-task windows are pinned to identical outputs
    (tests/test_mega.py); the per-task path remains the reference
    semantics.
    ``on_round``: called with a ``RoundRecord`` after each round of each
    task that had submitters (per-task and megastep alike); none is
    built without it.
    """

    def __init__(self, node, *, window: float = 1.0, seal_every: int = 0,
                 background=None, fused="auto", megabatch="auto",
                 on_round: Optional[Callable[[RoundRecord], None]] = None):
        self.node = node
        self.on_round = on_round
        self.window = window
        self.seal_every = seal_every
        self.background = background
        self.fused = fused
        self.megabatch = megabatch
        self.mega_windows = 0       # windows driven by the megastep path
        self._mega = None           # (cohort-id key, cached MegaCohort)
        self._loop = None           # active FusedWindowLoop during run()
        self.runtimes: List[TaskRuntime] = []
        self._bg_pos = 0
        # typed-event records collected by run() through the node's
        # client (core/events.py) — the scheduler observes settlement
        # through the public stream instead of poking ledger internals
        self.window_records: List[object] = []
        self.settlement_records: List[object] = []

    def add_task(self, task, cohort, **task_kw) -> TaskRuntime:
        """Register a task: ``task`` is an ``repro.api.FLTaskSpec`` (the
        public form) or a task-id string with FLTaskSpec's fields as loose
        kwargs (``rounds=``, ``reward=``, ``n_select=``, ``start_window=``,
        ``init_seed=``) — defaults live on FLTaskSpec alone."""
        from repro.api.specs import as_task_spec
        task = as_task_spec(task, **task_kw)
        rt = TaskRuntime(self.node, task.task_id, cohort, rounds=task.rounds,
                         reward=task.reward, n_select=task.n_select,
                         init_seed=task.init_seed)
        rt.start_window = task.start_window
        rt.on_round = self.on_round
        self.runtimes.append(rt)
        return rt

    def _seal_rollup(self):
        """Seal every pending rollup tx: all LedgerBackend rollup faces
        (object Rollup, VectorRollup, ShardedRollup) expose ``seal()``;
        the sharded fabric also records its fabric root here — this call
        IS the window-boundary commitment."""
        if self._loop is not None:
            self._loop.seal()
        else:
            self.node.rollup.seal()

    def _submit_background(self, t_end: float):
        if self.background is None:
            return
        txs = self.background.txs
        i = self._bg_pos
        j = int(np.searchsorted(txs.submit_time, t_end, side="left"))
        if j <= i:
            return
        chain = self.node.chain
        if getattr(chain, "soa_native", False):
            from repro.core.engine import TxArrays
            # remap raw workload sender ids into the chain's namespace
            # (the same "client<k>" actors the object engine sees) — raw
            # ids would collide with protocol senders registered via
            # chain.sender_id()
            sid = txs.sender_id[i:j]
            uniq = np.unique(sid)
            lut = np.array([chain.sender_id(f"client{int(u)}")
                            for u in uniq], np.int32)
            batch = TxArrays(
                txs.submit_time[i:j], txs.gas[i:j], txs.fn_id[i:j],
                lut[np.searchsorted(uniq, sid)], txs.fns)
            if self._loop is not None:
                self._loop.submit(chain, batch)
            else:
                chain.submit_arrays(batch)
        else:
            from repro.core.ledger import Tx
            for k in range(i, j):
                chain.submit(Tx(txs.fns.names[txs.fn_id[k]],
                                f"client{int(txs.sender_id[k])}", {},
                                int(txs.gas[k]), float(txs.submit_time[k])))
        self._bg_pos = j

    # -- cross-task megastep ---------------------------------------------------
    def _mega_eligible(self, rts: List[TaskRuntime]) -> bool:
        """One megastep can replace this window's per-task loop iff every
        stepping task is mid-round on the SAME compiled cohort program and
        the node's L2 target takes SoA batches.  Mixed-phase windows
        (select/settle interleavings) fall back silently — they are
        inherently sequential; capability gaps raise under
        ``megabatch=True``."""
        if not self.megabatch or self.background is not None:
            return False
        if any(rt.phase != "round" for rt in rts):
            return False
        node = self.node
        cohorts = [rt.cohort for rt in rts]
        target = node._target()
        ok = (getattr(target, "soa_native", False)
              and node.val_slices is not None
              and node.val_slices.stacked is not None
              and all(isinstance(c, VectorCohort) for c in cohorts)
              and all(c.kernels is cohorts[0].kernels for c in cohorts)
              and len({len(rt.sel_idx) for rt in rts}) == 1
              # sharded fabric: megabatched emission needs explicit pins
              # (least-loaded routing is submit-call-granularity dependent)
              and (not hasattr(target, "shards")
                   or all(rt.shard is not None for rt in rts))
              and (_eval_cache_get(_eval_cache_key(node.eval_fn))
                   is not _UNBATCHABLE))
        if not ok and self.megabatch is True:
            raise RuntimeError(
                "Scheduler(megabatch=True): window is not megabatchable "
                "(needs a SoA-native target, stacked validation slices, "
                "VectorCohorts sharing one CohortKernels, uniform cohort "
                "size, and shard pins on a fabric)")
        return ok

    def _mega_window(self, rts: List[TaskRuntime]) -> List[TaskRuntime]:
        """Run one round for EVERY task in ``rts`` as a single megastep.

        Bit-exact to stepping each TaskRuntime._round in order: training,
        scoring and Eq. 1 aggregation are task-independent along the vmap
        axis, tx stamp times are order-preserving under one concatenated
        emission, and per-cohort participation rngs are independent streams
        (tests/test_mega.py pins all of it element-wise)."""
        node = self.node
        self.mega_windows += 1
        # the MegaCohort is cached across windows so its stacked opt state
        # stays resident between consecutive megasteps of the same group
        # (keyed by the cohort objects themselves, not id() — rule R003)
        key = tuple(rt.cohort for rt in rts)
        if self._mega is None or self._mega[0] != key:
            self._mega = (key, MegaCohort([rt.cohort for rt in rts]))
        mega = self._mega[1].train(
            [rt.params for rt in rts], [rt.rnd for rt in rts],
            [rt.sel_idx for rt in rts])
        for rt in rts:
            rt.rnd += 1
        obs.count("fl.rounds", len(rts))
        groups = []
        for i, rt in enumerate(rts):
            subs = mega.subs[i]
            if subs is None:
                continue
            senders = []
            for j in subs.idxs:
                tid = node.trainer_ids[j]
                node.tsc.submit_local_model(tid, rt.task_id, rt.rnd - 1,
                                            subs.cids[j])
                senders.append(tid)
            groups.append(("submitLocalModel", senders, rt.shard))
            groups.append(("calculateObjectiveRep", senders, rt.shard))
            rt.completed[subs.idxs] += 1.0
        node._tx_batch_many(groups)
        tables = None
        if mega.active:
            try:
                tables = mega_score_tables(node.eval_fn, mega.raw,
                                           node.val_slices)
            except Exception:
                # eval_fn turned out non-vmappable: score per task, and
                # cache the verdict so later windows skip the megastep
                # entirely via _mega_eligible
                _eval_cache_put(_eval_cache_key(node.eval_fn), _UNBATCHABLE)
                tables = None
        scores = np.zeros((len(mega.active), len(rts[0].sel_idx)),
                          np.float32)
        params_in = [rts[t].params for t in mega.active]
        tables_out = []
        for a, t in enumerate(mega.active):
            subs = mega.subs[t]
            if tables is not None:
                s, report = quorum_from_table(tables[a][:, mega.pos[a]],
                                              node.don)
            else:
                s, report = evaluate_quorum(
                    node.eval_fn, subs.stacked, None, node.don,
                    slices=node.val_slices)
            obs.count("fl.eval_images",
                      len(subs.idxs) * node.val_slices.n_images)
            scores[a, :len(subs.idxs)] = np.asarray(s, np.float32)
            rts[t].last_scores = scores[a, :len(subs.idxs)].copy()
            tables_out.append(report["table"])
        # every active task merges over K rows (zero rows and zero weights
        # past its submitters): ONE vmapped Eq. 1 dispatch
        with obs.span("fl.aggregate"):
            if mega.active:
                n_rows = int(jax.tree.leaves(mega.sorted)[0].shape[0])
                smat = np.concatenate(
                    [scores, np.repeat(scores[:1], n_rows - len(scores), 0)])
                newp = _unstack_fn(n_rows)(weighted_average_tree_mega(
                    mega.sorted, jnp.asarray(smat)))
                for a, t in enumerate(mega.active):
                    rts[t].params = newp[a]
        for a, t in enumerate(mega.active):
            rt, subs = rts[t], mega.subs[t]
            node.tsc.advance_round(rt.task_id)
            rt.last_subs = subs
            if rt.on_round is not None:
                rt.on_round(RoundRecord(rt.task_id, params_in[a], subs.idxs,
                                        subs.stacked, tables_out[a],
                                        rt.last_scores, rt.params))
        for i, rt in enumerate(rts):
            if mega.subs[i] is None:
                node.tsc.advance_round(rt.task_id)
        ready = []
        for rt in rts:
            if rt.rnd >= rt.rounds:
                rt._finalize()
                ready.append(rt)
        return ready

    def run(self) -> Dict[str, object]:
        """Drive every task added since the last run to completion;
        returns {task_id: FLTaskResult}.

        Window/settlement provenance is consumed from the node's typed
        event stream (``client.events()``): after the run,
        ``self.window_records`` holds the ``WindowSettled`` commitments
        (fabric roots on a sharded node) and ``self.settlement_records``
        the ``AggregateVerified`` postings, in emission order.
        """
        node = self.node
        # tasks an earlier run settled leave the scheduler: a node that
        # runs epoch after epoch keeps only the tasks it is driving
        self.runtimes = [rt for rt in self.runtimes if rt.phase != "done"]
        client = node.client()
        # this run's provenance only: fast-forward past events emitted
        # before the run (a fresh client's cursor starts at the stack's
        # genesis), and collect into fresh record lists
        client.events()
        self.window_records, self.settlement_records = [], []
        from repro.core.fused import FusedWindowLoop, supports_fused
        use_fused = (supports_fused(node.chain, node.rollup)
                     if self.fused == "auto" else bool(self.fused))
        if self.fused == "auto" and not use_fused:
            # the fallback used to be silent; say it once per stack shape
            # (NodeClient.capabilities() surfaces the chosen path too)
            key = (type(node.chain).__name__,
                   type(node.rollup).__name__
                   if node.rollup is not None else None)
            if key not in _FUSED_FALLBACK_WARNED:
                _FUSED_FALLBACK_WARNED.add(key)
                _log.info(
                    "Scheduler(fused='auto'): %s/%s is not fused-capable; "
                    "using the Python-stepped window loop", *key)
        if use_fused:
            self._loop = FusedWindowLoop(node.chain, node.rollup)
            node._fused = self._loop
        # keep the shared mempool time-sorted: before every protocol
        # emission, background txs stamped earlier than the clock are
        # drained in (both engines pack FIFO and head-of-line-stall on
        # out-of-order future stamps — see Chain.produce_block)
        node.pre_tx_hook = self._submit_background
        w = 0
        t = 0.0
        try:
            while any(rt.phase != "done" for rt in self.runtimes):
                # the window END tracks the protocol clock: emitting n txs
                # advances the clock by 0.01*n, and a window edge behind
                # the clock would strand late-stamped protocol txs across
                # block boundaries
                node._clock = max(node._clock, t)
                stepping = [rt for rt in self.runtimes
                            if rt.phase not in ("settle_ready", "done")
                            and rt.start_window <= w]
                if stepping and self._mega_eligible(stepping):
                    ready = self._mega_window(stepping)
                else:
                    ready = []
                    for rt in stepping:
                        rt.step()
                        if rt.phase == "settle_ready":
                            ready.append(rt)
                if ready:
                    node.settle_window(ready)
                if self.seal_every and node.rollup is not None and \
                        (w + 1) % self.seal_every == 0:
                    self._seal_rollup()
                t_end = max(t + self.window, node._clock)
                self._submit_background(t_end)
                if node.rollup is not None:
                    # proof jobs drain on the shared window clock; pump
                    # BEFORE block production so window-finalized
                    # settlements land in the blocks that pack this window
                    (self._loop or node.rollup).pump(t_end)
                (self._loop or node.chain).run_until(t_end)
                t = t_end
                w += 1
                assert w < 1_000_000, "scheduler failed to make progress"
            self._submit_background(float("inf"))
            if node.rollup is not None:
                (self._loop or node.rollup).flush()
            t_end = node._clock + 5.0
            if self.background is not None:
                t_end = max(t_end, self.background.duration + 5.0)
            (self._loop or node.chain).run_until(t_end)
            if self._loop is not None:
                # replay the whole recorded window loop as one pass:
                # vectorized multi-window seals + one block-pack kernel
                self._loop.execute()
        finally:
            node.pre_tx_hook = None
            node._fused = None
            self._loop = None
        for ev in client.events():
            if ev.kind == "window_settled":
                self.window_records.append(ev)
            elif ev.kind == "aggregate_verified":
                self.settlement_records.append(ev)
        return {rt.task_id: rt.result for rt in self.runtimes}
