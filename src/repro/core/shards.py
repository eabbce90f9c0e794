"""Sharded rollup fabric: K L2 sequencers over one L1, one array state.

``ShardedRollup`` horizontally scales the L2 layer past a single
sequencer's throughput: K ``VectorRollup`` shards each own

  * their own sequencer lanes (batches seal concurrently within a shard
    AND across shards — the fabric latency is the slowest shard's),
  * a partition of the SoA account state (``StateArrays`` rows, owner =
    account id mod K),

and all post commit / verify / execute transactions to ONE shared L1
``VectorChain``, so the consensus layer stays unified while sequencing
capacity scales linearly.

Routing: per-transaction ``hash`` routing (stable xor-mix of the sender
id — an account's txs always land on the shard that owns its state rows)
or ``least_loaded`` (whole submissions to the emptiest shard).  Task-level
routing for the FL protocol (fl/scheduler.py) pins every transaction of a
task to one shard via ``assign_task`` + ``submit_arrays(..., shard=k)``.

Commitment: every ``seal()`` (the scheduler calls it at window boundaries)
records a **fabric root** — one sha256 merging the K per-shard partition
roots (``StateArrays.partition_root``) — into ``fabric_roots``.  The flat
array state root (``state_root()``) is chunked independently of K, so the
same transaction set commits to the same state root at any shard count
(pinned by tests/test_shards.py); state handlers must therefore be
per-account commutative (see core/state.py).

``n_shards=1`` is bit-equivalent to a plain ``VectorRollup`` — same
gas_log rows, same L1 stream, same digests (pinned by tests).

Security caveat: roots here are validity stand-ins, not zk proofs — see
core/rollup.py.
"""
from __future__ import annotations

import hashlib
import math
from functools import reduce
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from repro.core.engine import FnRegistry, TxArrays, VectorRollup
from repro.core.events import EventLog, WindowSettled
from repro.core.gas import DEFAULT_GAS, ROLLUP_BATCH, GasTable
from repro.core.interconnect import InterconnectSpec
from repro.core.ledger import EventHooks
from repro.core.prover import ProverPipeline
from repro.core.state import StateArrays, account_owner


def _hash_route(sender_id: np.ndarray, n_shards: int) -> np.ndarray:
    """Stable per-tx shard assignment — ``state.account_owner``, the SAME
    partition function ``StateArrays.partition_root`` commits rows with,
    so every tx of a sender lands on the shard owning that account's
    state rows (account-aligned; deterministic, no ``hash`` salt)."""
    return account_owner(sender_id, n_shards)


class ShardedRollup(EventHooks):
    """K-shard L2 fabric over one shared L1 (LedgerBackend face)."""

    soa_native = True
    # the fused loop replays the fabric as one plan: routing decisions
    # (hash split / least-loaded argmin / task pins) are taken at RECORD
    # time against the live ``_submitted`` counters, and execute() seals
    # the K lanes per window in shard order before ``_finish_window`` —
    # bit-identical to the stepped path, so Scheduler(fused="auto")
    # takes the fused loop here too
    fused_capable = True

    def __init__(self, l1, n_shards: int = 1,
                 batch_size: int = ROLLUP_BATCH,
                 gas_table: GasTable = DEFAULT_GAS,
                 prove_time: float = 0.9, per_tx_time: float = 0.14,
                 n_lanes: int = 1, route: str = "hash",
                 state: Optional[StateArrays] = None,
                 agg_width: int = 1, prover_capacity: int = 1,
                 finalize: str = "eager",
                 interconnect: Optional[InterconnectSpec] = None,
                 mesh: str = "auto"):
        assert n_shards >= 1
        assert route in ("hash", "least_loaded"), route
        assert mesh in ("auto", "on", "off"), mesh
        self.l1 = l1
        self.n_shards = n_shards
        self.route = route
        l1_fns = getattr(l1, "fns", None)
        self.fns: FnRegistry = l1_fns if l1_fns is not None else FnRegistry()
        # ONE typed event stream and ONE prover pipeline for the whole
        # fabric: shard events interleave in the L1's log under a single
        # seq, and job/session/aggregate ids are fabric-global (each
        # shard still closes its own sessions — the L1 sees K
        # independent proof aggregations, as before)
        l1_events = getattr(l1, "events", None)
        self.events = l1_events if l1_events is not None else EventLog()
        self.prover = ProverPipeline(
            gas_table, agg_width=agg_width, capacity=prover_capacity,
            prove_time=prove_time, finalize=finalize, events=self.events)
        self.shards: List[VectorRollup] = []
        for k in range(n_shards):
            s = VectorRollup(l1, batch_size=batch_size, gas_table=gas_table,
                             prove_time=prove_time, per_tx_time=per_tx_time,
                             n_lanes=n_lanes, prover=self.prover)
            s.fns = self.fns          # one fn namespace across the fabric
            s._event_shard = k        # shard tag on the shard's events
            s._suppress_window_event = True   # the fabric's is the window
            self.shards.append(s)
        self.batch_size = batch_size
        self.gas_table = gas_table
        # ONE fabric-wide sender/account namespace: ids index StateArrays
        # rows AND drive hash routing, so they must not be per-shard
        self._sender_ids: Dict[str, int] = {}
        self.state = state
        self.task_shard: Dict[str, int] = {}
        self._task_counts = np.zeros(n_shards, np.int64)
        self._submitted = np.zeros(n_shards, np.int64)
        self.fabric_roots: List[Dict[str, Any]] = []
        self._window = 0
        # explicit wire-cost model (core/interconnect.py): a parallel,
        # deterministic ledger of what crossing the fabric would cost —
        # it NEVER feeds the Table-II latency()/throughput() numbers
        self.interconnect = (interconnect if interconnect is not None
                             else InterconnectSpec()).build(n_shards)
        # "auto"/"on"/"off": whether the fused loop folds the K lanes'
        # seal digests through the mesh-mapped shard_seal kernel
        # (kernels/shard_lanes.py) instead of the host-local impls
        self.mesh_mode = mesh
        self._init_events()

    # -- events (NodeClient subscription hook) ---------------------------------
    def subscribe(self, event: str, callback: Callable) -> None:
        """``"window_settled"`` fires once per fabric seal (payload = the
        fabric-root record); ``"batch_sealed"``/``"session_settled"``
        forward from every shard with a ``"shard"`` key added."""
        if event == "window_settled":
            self._subs.setdefault(event, []).append(callback)
            return
        for k, s in enumerate(self.shards):
            s.subscribe(event,
                        lambda payload, k=k: callback(dict(payload, shard=k)))

    # -- LedgerBackend surface -------------------------------------------------
    def sender_id(self, sender: str) -> int:
        return self._sender_ids.setdefault(sender, len(self._sender_ids))

    def register_state(self, fn: str, handler: Callable):
        """Attach a StateArrays handler to every shard, all writing the
        ONE shared fabric state.  Handlers must be per-account commutative
        (counters/accumulators): each shard executes only the txs routed
        to it, and the merged state must not depend on the partition."""
        if self.state is None:
            self.state = StateArrays()
            self.state.enable_dirty_tracking()
        for s in self.shards:
            s.state_arrays = self.state
            s.register_state(fn, handler)

    def submit(self, tx):
        """Object-Tx compatibility shim (fabric sender namespace)."""
        batch = TxArrays.from_txs([tx], self.fns)
        batch.sender_id = np.array([self.sender_id(tx.sender)], np.int32)
        return self.submit_arrays(batch)

    def submit_arrays(self, batch: TxArrays, shard: Optional[int] = None):
        """Route a SoA batch into the fabric.

        ``shard=k`` pins the whole batch (task-level routing); otherwise
        ``hash`` splits per tx by sender and ``least_loaded`` sends the
        batch to the shard with the fewest submitted txs.

        Returns per-tx provenance in input order: ``(shard_of, seq_of)``
        int64 arrays — the owning shard and the sequence number the shard
        assigned (``VectorRollup.submit_arrays`` ranges), which receipts
        resolve to batches via ``shards[k].batch_of_seq``."""
        if batch.fns is not self.fns:
            remap = np.array([self.fns.id(n) for n in batch.fns.names],
                             np.int32)
            batch = TxArrays(batch.submit_time, batch.gas,
                             remap[batch.fn_id] if len(batch) else
                             batch.fn_id, batch.sender_id, self.fns)
        n = len(batch)
        if shard is None and self.route == "least_loaded":
            shard = int(np.argmin(self._submitted))
        if shard is not None or self.n_shards == 1:
            k = int(shard or 0)
            self._submitted[k] += n
            pinned = np.zeros(self.n_shards, np.int64)
            pinned[k] = n
            self._wire_submit(pinned)
            lo, hi = self.shards[k].submit_arrays(batch)
            return (np.full(n, k, np.int64),
                    np.arange(lo, hi, dtype=np.int64))
        lanes = _hash_route(batch.sender_id, self.n_shards)
        self._wire_submit(np.bincount(lanes, minlength=self.n_shards))
        seq_of = np.empty(n, np.int64)
        for k in range(self.n_shards):
            m = lanes == k
            if m.any():
                self._submitted[k] += int(m.sum())
                lo, hi = self.shards[k].submit_arrays(TxArrays(
                    batch.submit_time[m], batch.gas[m], batch.fn_id[m],
                    batch.sender_id[m], self.fns))
                seq_of[m] = np.arange(lo, hi, dtype=np.int64)
        return lanes.astype(np.int64), seq_of

    def _wire_submit(self, counts) -> None:
        """Account the cohort->shard wire cost of one routed submission
        (``counts`` = txs per destination shard).  Called at ROUTING time
        on both the stepped and the fused path, so the wire logs match."""
        if int(np.sum(counts)):
            self.interconnect.record_submit(counts)

    # -- task-level routing (protocol layer) -----------------------------------
    def assign_task(self, task_id: str) -> int:
        """Pin a task to a shard: stable content hash of the task id, or
        the shard with the fewest assigned tasks (``least_loaded``)."""
        k = self.task_shard.get(task_id)
        if k is None:
            if self.route == "least_loaded":
                k = int(np.argmin(self._task_counts))
            else:
                h = hashlib.sha256(task_id.encode()).digest()
                k = int.from_bytes(h[:8], "big") % self.n_shards
            self.task_shard[task_id] = k
            self._task_counts[k] += 1
        return k

    # -- sequencing / settlement -----------------------------------------------
    def seal(self) -> int:
        """Seal every shard's pending txs; record the fabric root.

        Window-boundary contract (fl/scheduler.py): after all shards seal,
        the K partition roots are merged into one fabric root — the
        cross-shard commitment for this window."""
        return self._finish_window([s.seal() for s in self.shards])

    def _finish_window(self, shard_batches: List[int]) -> int:
        """Merge one window after every shard sealed: account the
        root-gather wire cost, record the fabric root and emit the
        ``WindowSettled`` event.  The fused loop (core/fused.py) calls
        this directly after applying the K precomputed lane seals —
        same record, same event, same window counter."""
        nb = int(sum(shard_batches))
        self.interconnect.record_root_gather(self._window, shard_batches)
        record: Dict[str, Any] = {"n_batches": nb}
        if self.state is not None:
            record = self._root_record(nb)
            self.fabric_roots.append(record)
        self.events.emit(
            WindowSettled,
            time=max((s._last_time for s in self.shards), default=0.0),
            window=self._window, n_batches=nb,
            state_root=record.get("state_root", ""),
            fabric_root=record.get("fabric_root", ""),
            shard_roots=tuple(record.get("shard_roots", ())))
        self._window += 1
        self._emit("window_settled", record)
        return nb

    @staticmethod
    def _merge_roots(shard_roots: List[str]) -> str:
        h = hashlib.sha256()
        for r in shard_roots:
            h.update(r.encode())
        return h.hexdigest()[:32]

    def _root_record(self, n_batches: int) -> Dict[str, Any]:
        shard_roots = self.state.partition_roots(self.n_shards)
        return {"window": len(self.fabric_roots), "n_batches": n_batches,
                "state_root": self.state.root(),
                "fabric_root": self._merge_roots(shard_roots),
                "shard_roots": shard_roots}

    def fabric_root(self) -> str:
        """Current merged commitment (computed on demand from the K
        partition roots alone; ``seal``/``flush`` append the fuller
        per-window records — including the flat state root — to
        ``fabric_roots``)."""
        if self.state is None:
            return ""
        return self._merge_roots(self.state.partition_roots(self.n_shards))

    def state_root(self) -> str:
        return self.state.root() if self.state is not None else ""

    def settle_session(self):
        """Per-shard zkSync-style settlement through the ONE shared
        prover pipeline: each shard closes its own session (the L1 sees
        K independent proof aggregations, folded per the fabric's
        aggregation width)."""
        for s in self.shards:
            s.settle_session()

    def pump(self, now: float) -> int:
        """Drain the fabric's modeled prover to ``now``."""
        return self.prover.pump(now)

    def flush(self):
        self.seal()
        self.settle_session()
        self.prover.drain()

    # -- merged views ----------------------------------------------------------
    @property
    def gas_log(self) -> List[Dict[str, Any]]:
        """Merged per-batch rows in (shard, row) order; n_shards=1 yields
        exactly the single shard's rows (plus the ``shard`` tag)."""
        out = []
        for k, s in enumerate(self.shards):
            for r in s.gas_log:
                row = dict(r)
                row["shard"] = k
                out.append(row)
        return out

    @property
    def n_batches(self) -> int:
        return sum(s.n_batches for s in self.shards)

    @property
    def batch_digests(self) -> List[int]:
        return [d for s in self.shards for d in s.batch_digests]

    @property
    def update_digest(self) -> int:
        return reduce(lambda a, b: a ^ b,
                      (s.update_digest for s in self.shards))

    # -- metrics ---------------------------------------------------------------
    def throughput(self, l1_tps: float) -> float:
        """Paper's method, scaled by concurrently sequencing shards."""
        return sum(s.throughput(l1_tps) for s in self.shards)

    def latency(self, n_calls: int) -> float:
        """Table-II latency model: shards sequence concurrently, so the
        fabric session latency is the slowest shard's share.

        The share is the fabric's ACTUAL routed distribution (observed
        ``_submitted`` counts, scaled to ``n_calls``) — a skewed router
        shows up as a slow fabric instead of being modeled away.  A fresh
        fabric with no observed traffic falls back to an even split."""
        total = int(self._submitted.sum())
        if total > 0:
            return max(s.latency(math.ceil(n_calls * int(c) / total))
                       for s, c in zip(self.shards, self._submitted) if c)
        per_shard = math.ceil(n_calls / self.n_shards)
        return max(s.latency(per_shard) for s in self.shards)

    def sealed_batch_throughput(self, n_calls: int) -> float:
        """Modeled sealed-batch throughput at a fixed workload: txs per
        modeled fabric-session second (benchmarks/bench_shards.py)."""
        return n_calls / max(self.latency(n_calls), 1e-12)
