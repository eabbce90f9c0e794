"""Decentralized FL protocol node: tasks + trainers + DON + reputation
+ escrow + rollup, wired together (the full paper workflow, steps 1-16 of
Fig. 1).  No central server: the 'orchestrator' here is the protocol state
machine every node can replay from the ledger.

``AutoDFL`` owns the SHARED protocol state (chain/rollup, escrow, blob
store, reputation book, clock); the per-task round logic lives in
``fl/scheduler.TaskRuntime``.  ``run_task`` drives one TaskRuntime to
completion sequentially; ``fl/scheduler.Scheduler`` interleaves many on the
same node — the paper's multi-task congestion scenario."""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro import obs
from repro.core.escrow import Escrow
from repro.core.gas import DEFAULT_GAS
from repro.core.ledger import AccessControl, Tx
from repro.core.oracle import DONConfig, ValidationSlices
from repro.core.reputation import (ReputationParams, TrainerBook,
                                   end_of_multitask_update, init_book,
                                   sync_book_to_state)
from repro.core.state import default_state_handlers
from repro.core.storage import BlobStore
from repro.core.tasks import TaskContract


@dataclasses.dataclass
class FLTaskResult:
    global_params: object
    scores: np.ndarray
    reputations: np.ndarray
    payouts: Dict[str, float]
    diagnostics: List[Dict]


class AutoDFL:
    """End-to-end protocol harness (the PoC the paper evaluates).

    Construction is spec-driven (``spec=repro.api.NodeSpec(...)`` — the
    public path); the legacy flag kwargs (``engine=``, ``use_rollup=``,
    ``n_shards=``, ``shard_route=``) still work for one release through
    ``NodeSpec.from_legacy`` with a DeprecationWarning.  Both paths build
    the ledger through ``repro.api.build_stack`` and are pinned
    equivalent (same state root, same gas) by tests/test_api.py.
    """

    #: legacy ctor kwargs folded into NodeSpec.from_legacy, with defaults
    _LEGACY_DEFAULTS = {"engine": "object", "use_rollup": True,
                        "n_shards": 1, "shard_route": "hash",
                        "trainer_funds": 10.0, "publisher_funds": 1000.0}

    def __init__(self, model, opt, n_trainers: int,
                 eval_fn: Callable, val_batch,
                 rep_params: Optional[ReputationParams] = None,
                 don: Optional[DONConfig] = None,
                 use_rollup: Optional[bool] = None,
                 seed: Optional[int] = None,
                 engine: Optional[str] = None,
                 trainer_funds: Optional[float] = None,
                 publisher_funds: Optional[float] = None,
                 n_shards: Optional[int] = None,
                 shard_route: Optional[str] = None, *,
                 spec: Optional["NodeSpec"] = None):
        from repro.api.factory import build_stack
        from repro.api.specs import NodeSpec
        legacy = {k: v for k, v in {
            "engine": engine, "use_rollup": use_rollup, "n_shards": n_shards,
            "shard_route": shard_route, "trainer_funds": trainer_funds,
            "publisher_funds": publisher_funds}.items() if v is not None}
        if spec is None:
            # deprecation shim: ledger-shape flags map onto a NodeSpec
            # (rep_params/don/funds kwargs stay silent — they are protocol
            # constants, not the flag wiring this shim retires)
            flags = {k: v for k, v in legacy.items()
                     if k in ("engine", "use_rollup", "n_shards",
                              "shard_route")}
            if flags:
                import warnings
                warnings.warn(
                    f"AutoDFL kwargs {sorted(flags)} are deprecated; pass "
                    "spec=repro.api.NodeSpec(...) (see docs/MIGRATION.md)",
                    DeprecationWarning, stacklevel=2)
            spec = NodeSpec.from_legacy(
                rep_params=rep_params, don=don, seed=seed or 0,
                **{**self._LEGACY_DEFAULTS, **legacy})
        else:
            # spec wins wholesale — reject every kwarg it would shadow so
            # nothing is silently dropped in a mixed call (ValueError, not
            # assert: the guard must survive python -O)
            if legacy or rep_params is not None or don is not None \
                    or seed is not None:
                raise ValueError(
                    "pass either spec= or legacy kwargs, not both")
            if spec.n_trainers not in (None, n_trainers):
                raise ValueError(
                    f"spec.n_trainers={spec.n_trainers} contradicts the "
                    f"positional n_trainers={n_trainers}")
        self.spec = spec
        self.model = model
        self.opt = opt
        self.eval_fn = eval_fn
        self.val_batch = val_batch
        # per-instance construction (a shared default ReputationParams()/
        # DONConfig() instance across all nodes was the old footgun)
        self.rep_params = spec.reputation.to_params()
        self.don = spec.don.to_config()
        trainer_funds = spec.trainer_funds
        publisher_funds = spec.publisher_funds
        self.val_slices = ValidationSlices(val_batch, self.don.n_oracles)

        self.store = BlobStore()
        self.acl = AccessControl(["admin0", "admin1", "admin2"])
        self.escrow = Escrow()
        self.tsc = TaskContract(self.acl, self.escrow, self.store)
        # ONE construction path for all five ledger backends
        self.chain, self.rollup = build_stack(spec)
        self.use_rollup = self.rollup is not None
        self.book: TrainerBook = init_book(n_trainers)
        self.trainer_ids = [f"trainer{i}" for i in range(n_trainers)]
        self._trainer_idx = {t: i for i, t in enumerate(self.trainer_ids)}
        for t in self.trainer_ids:
            self.acl.grant("admin0", t, "trainer")
            self.escrow.fund(t, trainer_funds)
        self.publisher = "tp0"
        self.acl.grant("admin0", self.publisher, "task_publisher")
        self.escrow.fund(self.publisher, publisher_funds)
        self._clock = 0.0
        # task-shard pin for the CURRENT emission (set by TaskRuntime.step
        # / settle_window when the L2 target is a ShardedRollup)
        self._route_shard: Optional[int] = None
        # array-native L2 account state (core/state.py): handlers written
        # once against StateArrays views run on every ledger face; rows
        # are indexed by the target's sender ids
        self.state_arrays = None
        self._wire_state()
        # protocol traffic accounting (the bench_protocol TPS numerator)
        self.protocol_calls: Dict[str, int] = {}
        # invoked with the current clock before every protocol emission;
        # the Scheduler uses it to drain background traffic in time order
        # (both engines pack FIFO and stall on out-of-order future stamps)
        self.pre_tx_hook: Optional[Callable[[float], None]] = None
        # active core/fused.py plan (set by Scheduler.run in fused mode):
        # protocol emissions and the end-of-window state sync route through
        # it so the whole window loop replays as one compiled pass
        self._fused = None

    def trainer_index(self, trainer_id: str) -> int:
        return self._trainer_idx[trainer_id]

    # -- ledger helpers -----------------------------------------------------------
    def _target(self):
        return self.rollup if self.rollup is not None else self.chain

    def client(self):
        """RPC-style façade over this node's ledger (repro.api.NodeClient):
        receipts (proof lifecycle), account views, state root, and the
        typed event stream (``client.events()``).  Shares the node's
        ledger and clock origin."""
        from repro.api.client import NodeClient
        return NodeClient(self._target(), self.chain,
                          gas_table=self.spec.chain.gas_table,
                          clock_start=self._clock)

    def _wire_state(self) -> None:
        """Attach the fixed-schema SoA account state + the default
        protocol counters to the L2 target (idempotent; tests that swap
        ``self.rollup`` for a ShardedRollup re-invoke it)."""
        target = self._target()
        if not hasattr(target, "register_state"):
            return
        for fn, handler in default_state_handlers().items():
            target.register_state(fn, handler)
        # the fabric keeps its StateArrays in ``state``; the single-rollup
        # faces in ``state_arrays`` (``state`` is their L2 dict there)
        from repro.core.state import StateArrays
        st = getattr(target, "state", None)
        self.state_arrays = st if isinstance(st, StateArrays) \
            else target.state_arrays

    def _sync_fabric_state(self) -> None:
        """Cross-shard end-of-window settlement: scatter the reputation
        book and escrow balances/stake into the fabric's StateArrays.
        These rows span every shard partition — the fabric root sealed at
        the next window boundary commits the merged result."""
        state = self.state_arrays
        if state is None:
            return
        target = self._target()
        ids = np.array([target.sender_id(t) for t in self.trainer_ids],
                       np.int64)
        locked = {}
        for per_task in self.escrow.collateral.values():
            for who, amount in per_task.items():
                locked[who] = locked.get(who, 0.0) + amount
        balances = [self.escrow.balances.get(t, 0.0)
                    for t in self.trainer_ids]
        stake = [locked.get(t, 0.0) for t in self.trainer_ids]
        # the scattered rows span every shard partition: account their
        # wire cost NOW (routing/record time — identical on the stepped
        # and fused paths) against the fabric's interconnect model
        ic = getattr(target, "interconnect", None)
        if ic is not None and len(ids):
            ic.record_settle_scatter(len(ids))
        if self._fused is not None:
            # window roots commit this scatter — journal it so the fused
            # replay applies it between the same seal points
            self._fused.sync_state(state, ids,
                                   np.asarray(self.book.reputation,
                                              np.float32), balances, stake)
            return
        sync_book_to_state(self.book, state, ids)
        state.balances[ids] = balances
        state.stake[ids] = stake
        state.mark_dirty(ids)

    def _tx(self, fn: str, sender: str, payload: Dict):
        self._tx_batch(fn, [sender], [payload])

    def _tx_batch(self, fn: str, senders: Sequence[str], payloads=None):
        """Emit one protocol tx per sender (clock-stamped 0.01s apart, same
        as sequential ``_tx`` calls) — one SoA append on the vector engine
        instead of a per-tx Python object.  ``payloads``: a list of dicts
        or a zero-arg callable producing one (only materialized on the
        object path; the SoA engine drops payloads by design)."""
        if not senders:
            return
        with obs.span("fl.emit"):
            n = len(senders)
            if self.pre_tx_hook is not None:
                self.pre_tx_hook(self._clock)
            target = self._target()
            gas = DEFAULT_GAS.l1_per_call.get(fn, 30000)
            times = self._clock + 0.01 * np.arange(1, n + 1)
            self._clock += 0.01 * n
            if getattr(target, "soa_native", False):
                from repro.core.engine import TxArrays
                # ids MUST come from the target's own namespace: _tx's submit
                # shim registers senders there, and mixing the chain's counter
                # into the rollup's stream would collide/misattribute ids
                sender_ids = np.array(
                    [target.sender_id(s) for s in senders], np.int32)
                fid = target.fns.id(fn)
                batch = TxArrays(times, np.full(n, gas, np.int64),
                                 np.full(n, fid, np.int32), sender_ids,
                                 target.fns)
                if self._fused is not None and self._fused.covers(target):
                    # the shard pin rides into the journaled plan — the fused
                    # loop replays task-pinned routing at record time
                    self._fused.submit(target, batch, shard=self._route_shard)
                elif self._route_shard is not None and hasattr(target, "shards"):
                    # task-pinned shard routing (core/shards.py fabric)
                    target.submit_arrays(batch, shard=self._route_shard)
                else:
                    target.submit_arrays(batch)
            else:
                if callable(payloads):
                    payloads = payloads()
                for k, s in enumerate(senders):
                    target.submit(Tx(fn, s,
                                     payloads[k] if payloads else {}, gas,
                                     float(times[k])))
            self.protocol_calls[fn] = self.protocol_calls.get(fn, 0) + n

    def _tx_batch_many(self, groups) -> None:
        """Megabatched emission: ``groups`` is ``[(fn, senders, shard)]``
        in the order sequential ``_tx_batch`` calls would have run.  Times
        are stamped over the concatenation exactly as those calls would
        stamp them (clock + 0.01 per tx), and the whole window's protocol
        traffic lands in ONE ``submit_arrays`` per destination shard —
        per-shard tx streams are identical to the per-task calls (submit
        only stages; batches/blocks form at seal time), while the
        interconnect model sees the coalesced routing messages (same
        bytes, fewer transfers — the megabatching win).  SoA targets only
        (payload callables are never materialized there)."""
        groups = [(fn, s, shard) for fn, s, shard in groups if s]
        if groups:
            with obs.span("fl.emit"):
                total = sum(len(s) for _, s, _ in groups)
                if self.pre_tx_hook is not None:
                    self.pre_tx_hook(self._clock)
                target = self._target()
                assert getattr(target, "soa_native", False), \
                    "_tx_batch_many needs a SoA-native target"
                from repro.core.engine import TxArrays
                times = np.empty(total, np.float64)
                gas = np.empty(total, np.int64)
                fn_id = np.empty(total, np.int32)
                sender_id = np.empty(total, np.int32)
                shard_of = np.full(total, -1, np.int64)
                o = 0
                for fn, senders, shard in groups:
                    n = len(senders)
                    # advance the clock group-by-group with _tx_batch's exact
                    # arithmetic — one flat arange over the concatenation drifts
                    # by ulps and un-pins event timestamps
                    times[o: o + n] = self._clock + 0.01 * np.arange(1, n + 1)
                    self._clock += 0.01 * n
                    gas[o: o + n] = DEFAULT_GAS.l1_per_call.get(fn, 30000)
                    fn_id[o: o + n] = target.fns.id(fn)
                    sender_id[o: o + n] = [target.sender_id(s) for s in senders]
                    if shard is not None:
                        shard_of[o: o + n] = shard
                    self.protocol_calls[fn] = self.protocol_calls.get(fn, 0) + n
                    o += n
                fused = self._fused if (self._fused is not None
                                        and self._fused.covers(target)) else None
                sharded = hasattr(target, "shards")
                if sharded:
                    assert (shard_of >= 0).all(), \
                        "megabatched emission on a fabric needs per-task shard pins"
                    dests = np.unique(shard_of)
                else:
                    dests = np.array([-1])
                for k in dests:
                    m = shard_of == k if sharded else slice(None)
                    batch = TxArrays(times[m], gas[m], fn_id[m], sender_id[m],
                                     target.fns)
                    pin = int(k) if sharded else None
                    if fused is not None:
                        fused.submit(target, batch, shard=pin)
                    elif pin is not None:
                        target.submit_arrays(batch, shard=pin)
                    else:
                        target.submit_arrays(batch)

    # -- fused end-of-task settlement (step 16, Eq. 2-10) -------------------------
    def settle_window(self, runtimes) -> None:
        """Settle every task that reached "settle_ready" in this window:
        ONE fused reputation update over all K cohorts (batched
        participation masks), then per-task score recording, escrow payout
        and reputation txs.  Row order = runtime order (deterministic)."""
        if not runtimes:
            return
        with obs.span("fl.settle"):
            n = len(self.trainer_ids)
            stack = lambda key: np.stack([getattr(rt, key) for rt in runtimes])
            rounds_total = np.stack([np.full(n, float(rt.rounds), np.float32)
                                     for rt in runtimes])
            self.book, diags = end_of_multitask_update(
                self.book, stack("score_auto"), stack("completed"), rounds_total,
                stack("dists"), stack("participated"), self.rep_params)
            reputations = np.asarray(self.book.reputation)
            s_rep = np.asarray(diags["s_rep"])
            for k, rt in enumerate(runtimes):
                self._route_shard = getattr(rt, "shard", None)
                try:
                    self._tx_batch(
                        "calculateSubjectiveRep",
                        [self.trainer_ids[i] for i in rt.sel_idx],
                        lambda k=k, rt=rt: [{"value": float(s_rep[k, i])}
                                            for i in rt.sel_idx])
                finally:
                    self._route_shard = None
                self.tsc.record_scores(rt.task_id, {
                    self.trainer_ids[i]: float(rt.score_auto[i])
                    for i in rt.sel_idx})
                payouts = self.tsc.close_task(rt.task_id)
                # the closed task's round models leave the node's blob store
                # (a long-running node unpins them once the task is settled)
                self.store.drop(self.tsc.retire_models(rt.task_id))
                diag_k = {key: np.asarray(v[k]) for key, v in diags.items()}
                rt.result = FLTaskResult(rt.params, rt.score_auto, reputations,
                                         payouts, [diag_k])
                rt.phase = "done"
            # cross-shard reputation settlement: commit the merged book/escrow
            # into the array state; the next window-boundary seal roots it
            self._sync_fabric_state()

    # -- one full task (steps 1-16 of Fig. 1), driven sequentially ----------------
    def run_task(self, task, agents, batch_fn=None,
                 **task_kw) -> FLTaskResult:
        """Sequential single-task driver over the TaskRuntime state machine
        (``agents``: a list of TrainingAgents or a fl/cohort.py cohort).
        ``task`` is an ``repro.api.FLTaskSpec`` or a task-id string with
        FLTaskSpec's fields as loose kwargs (``rounds=``, ``reward=``,
        ``n_select=``, ...) — defaults live on FLTaskSpec alone.
        ``Scheduler`` with this one task produces identical outputs —
        pinned by tests/test_scheduler.py."""
        from repro.api.specs import as_task_spec
        from repro.fl.scheduler import TaskRuntime
        task = as_task_spec(task, **task_kw)
        rt = TaskRuntime(self, task.task_id, agents, rounds=task.rounds,
                         reward=task.reward, n_select=task.n_select,
                         init_seed=task.init_seed)
        while rt.phase not in ("settle_ready", "done"):
            rt.step()
        self.settle_window([rt])
        if self.rollup is not None:
            self.rollup.flush()
        self.chain.run_until(self._clock + 5.0)
        return rt.result
