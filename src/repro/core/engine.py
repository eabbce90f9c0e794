"""Vectorized L1/L2 transaction engine (structure-of-arrays hot path).

The object-based simulator (core/ledger.py, core/rollup.py) processes every
transaction as a Python ``Tx`` in a FIFO loop — faithful to the paper's
Fig. 4 / Table I experiments but O(submitted txs) in Python bytecode.  This
module re-implements the same discrete-event semantics over NumPy arrays so
that one simulated block costs O(log n) (two ``searchsorted`` calls against
precomputed running-max/cumsum arrays) instead of O(txs in block) Python
iterations, and one rollup session costs a handful of vectorized passes.

Semantics contract (enforced by tests/test_engine.py):

  * ``VectorChain`` produces blocks with EXACTLY the same tx counts,
    gas_used, confirm times and total gas as ``ledger.Chain`` on the same
    workload — including the head-of-line FIFO rule: block packing walks
    the mempool in submission order and stops at the first transaction
    whose ``submit_time`` is in the future OR whose gas would overflow the
    block, without skipping ahead.  A future-timestamped tx at the head of
    an out-of-order mempool therefore stalls everything behind it (in both
    engines); ``simulate_load``/``Workload`` guard against accidental skew
    by always submitting in sorted time order.
  * ``VectorRollup`` with ``n_lanes=1`` writes the same ``gas_log`` rows
    (commit / amortized verify / execute per batch) as ``rollup.Rollup``.

Digests: each seal folds the batch's transaction words through the
ledger's xor-mix (``kernels/digest.py``), by the kernel the factory picks:
the Pallas kernels on a TPU, the bit-exact NumPy mirrors elsewhere
(``xor_fold_digest`` is the scalar one; pinned by tests/test_engine.py).
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core.events import BatchSealed, BlockPacked, EventLog
from repro.core.gas import DEFAULT_GAS, ROLLUP_BATCH, GasTable
from repro.core.ledger import EventHooks
from repro.core.prover import ProverFace, ProverPipeline, session_latency
from repro.core.state import Registry
from repro.kernels.digest import MIX_SEED


def xor_fold_digest_segments(words: np.ndarray,
                             starts: np.ndarray) -> np.ndarray:
    """Segmented fold: one digest per ``[starts[i], starts[i+1])`` word
    range (u32 array) — the multi-batch form VectorRollup.seal uses.
    Routed through the kernel factory (op ``"batch_seal"``): the NumPy
    mirror on CPU, the Pallas segment kernel on TPU, overridable via
    ``REPRO_KERNEL_IMPL`` (see kernels/factory.py)."""
    from repro.kernels.factory import get_kernel
    return get_kernel("batch_seal")(words, starts)


def update_buffer_digest(words: np.ndarray) -> int:
    """The merged update buffer's digest (one xor-mix fold over all of
    it), on the kernel factory's ``"rollup_digest"`` op."""
    from repro.kernels.factory import get_kernel
    return int(get_kernel("rollup_digest")(words))


class FnRegistry(Registry):
    """Stable fn-name <-> integer-id mapping shared across SoA batches
    (the function-namespace face of core/state.py's generic Registry)."""


@dataclasses.dataclass
class TxArrays:
    """Structure-of-arrays transaction batch (the vector engine's Tx)."""

    submit_time: np.ndarray          # float64 (N,)
    gas: np.ndarray                  # int64   (N,)
    fn_id: np.ndarray                # int32   (N,)
    sender_id: np.ndarray            # int32   (N,)
    fns: FnRegistry

    def __post_init__(self):
        self.submit_time = np.asarray(self.submit_time, np.float64)
        self.gas = np.asarray(self.gas, np.int64)
        self.fn_id = np.asarray(self.fn_id, np.int32)
        self.sender_id = np.asarray(self.sender_id, np.int32)

    def __len__(self) -> int:
        return self.submit_time.shape[0]

    @classmethod
    def homogeneous(cls, fn: str, times: np.ndarray, gas: int,
                    n_senders: int = 64,
                    fns: Optional[FnRegistry] = None) -> "TxArrays":
        """One function type at fixed per-call gas (the Fig. 4 workload)."""
        fns = fns or FnRegistry()
        n = len(times)
        return cls(np.asarray(times, np.float64),
                   np.full(n, gas, np.int64),
                   np.full(n, fns.id(fn), np.int32),
                   (np.arange(n) % max(1, n_senders)).astype(np.int32), fns)

    @classmethod
    def from_txs(cls, txs: Sequence[Any],
                 fns: Optional[FnRegistry] = None) -> "TxArrays":
        """Compatibility shim: lift object ``Tx`` lists into SoA form."""
        fns = fns or FnRegistry()
        senders: Dict[str, int] = {}
        sid = np.empty(len(txs), np.int32)
        fid = np.empty(len(txs), np.int32)
        for i, t in enumerate(txs):
            fid[i] = fns.id(t.fn)
            sid[i] = senders.setdefault(t.sender, len(senders))
        return cls(np.array([t.submit_time for t in txs], np.float64),
                   np.array([t.gas for t in txs], np.int64), fid, sid, fns)

    def word_buffer(self) -> np.ndarray:
        """Interleaved u32 words (time bits, gas, fn, sender) for digests."""
        n = len(self)
        w = np.empty(4 * n, np.uint32)
        w[0::4] = self.submit_time.astype(np.float32).view(np.uint32)
        w[1::4] = (self.gas & 0xFFFFFFFF).astype(np.uint32)
        w[2::4] = self.fn_id.astype(np.uint32)
        w[3::4] = self.sender_id.astype(np.uint32)
        return w


@dataclasses.dataclass
class BlockStats:
    """Vector-engine block record (counts + gas, not per-tx objects)."""
    height: int
    time: float
    n_txs: int
    gas_used: int
    start: int                 # [start, stop) tx index range in arrival order
    stop: int
    parent: str = ""
    block_hash: str = ""

    def __post_init__(self):
        if not self.block_hash:
            h = hashlib.sha256(
                (self.parent + ":" + str(self.height) + ":" +
                 str(self.start) + ":" + str(self.stop) + ":" +
                 str(self.gas_used)).encode()).hexdigest()
            self.block_hash = h[:16]


class VectorChain(EventHooks):
    """Vectorized mirror of ``ledger.Chain``: QBFT quorum, gas-limited FIFO
    block packing over SoA arrays, O(log n) per block."""

    EVENTS = ("block_packed",)

    # SoA is this face's NATIVE path (emitters dispatch batched emission on
    # this flag, not on submit_arrays presence — the object faces expose a
    # lowering submit_arrays adapter too, but drop nothing when fed Txs)
    soa_native = True
    # the SoA L1 can run under the core/fused.py plan-then-execute loop
    fused_capable = True

    def __init__(self, n_validators: int = 4, block_time: float = 1.0,
                 block_gas_limit: int = 9_000_000,
                 gas_table: GasTable = DEFAULT_GAS,
                 fns: Optional[FnRegistry] = None):
        assert n_validators >= 4, "QBFT needs >= 3f+1 with f >= 1"
        self.n_validators = n_validators
        self.block_time = block_time
        self.block_gas_limit = block_gas_limit
        self.gas_table = gas_table
        self.fns = fns or FnRegistry()
        self.blocks: List[BlockStats] = [BlockStats(0, 0.0, 0, 0, 0, 0,
                                                    "genesis")]
        self.state: Dict[str, Any] = {}
        self.total_gas = 0
        self._batch_handlers: Dict[int, Callable] = {}
        # LedgerBackend face: handlers written against (StateArrays,
        # TxArrays-view), called once per (block, fn) with the fn-filtered
        # confirmed slice (see ledger.LedgerBackend.register_state)
        self.state_arrays = None
        self._state_handlers: Dict[int, Callable] = {}
        self._sender_ids: Dict[str, int] = {}    # submit(tx) shim namespace
        # consolidated mempool arrays (arrival order, never reordered).
        # Geometric (doubling) capacity growth + incremental running-max /
        # cumsum extension keep consolidation amortized O(new txs), so the
        # O(log n)-per-block contract holds for interleaved submit/produce
        # producers, not just submit-everything-then-run ones.
        self._n = 0                              # filled prefix of buffers
        self._t = np.empty(0, np.float64)
        self._g = np.empty(0, np.int64)
        self._f = np.empty(0, np.int32)
        self._s = np.empty(0, np.int32)
        self._confirm = np.empty(0, np.float64)
        self._tmax = np.empty(0, np.float64)    # running max of _t
        self._gcum = np.empty(0, np.int64)      # running cumsum of _g
        self._ptr = 0                            # first unconfirmed index
        self._staged: List[TxArrays] = []
        self._staged_n = 0
        self._block_stops = np.empty(0, np.int64)   # block_of lookup cache
        # the stack-wide typed event stream (L1-owned; L2 faces adopt it)
        self.events = EventLog()
        self._init_events()

    # -- contract surface ------------------------------------------------------
    def register_batch(self, fn: str, handler: Callable):
        """Batched handler: handler(state, n_calls, tx_slice: TxArrays-view).
        Called once per (block, fn) instead of once per tx."""
        self._batch_handlers[self.fns.id(fn)] = handler

    def register_state(self, fn: str, handler: Callable):
        """StateArrays handler (LedgerBackend): handler(state_arrays, view)
        with ``view`` holding only ``fn``'s confirmed txs, block order."""
        if self.state_arrays is None:
            from repro.core.state import StateArrays
            self.state_arrays = StateArrays()
            self.state_arrays.enable_dirty_tracking()
        self._state_handlers[self.fns.id(fn)] = handler

    def state_root(self) -> str:
        return self.state_arrays.root() if self.state_arrays is not None \
            else ""

    def submit_arrays(self, batch: TxArrays):
        """Stage a SoA batch; returns the ``[lo, hi)`` global arrival-index
        range assigned to it (tx provenance: the index is stable across
        consolidation and is what ``block_of``/receipts resolve)."""
        if batch.fns is not self.fns:
            # remap fn ids into this chain's registry
            remap = np.array([self.fns.id(n) for n in batch.fns.names],
                             np.int32)
            batch = TxArrays(batch.submit_time, batch.gas,
                             remap[batch.fn_id] if len(batch) else
                             batch.fn_id, batch.sender_id, self.fns)
        lo = self._n + self._staged_n
        self._staged.append(batch)
        self._staged_n += len(batch)
        return lo, lo + len(batch)

    def sender_id(self, sender: str) -> int:
        """Stable sender-name -> id mapping for the object-Tx shim."""
        return self._sender_ids.setdefault(sender, len(self._sender_ids))

    def submit(self, tx):
        """Object-Tx compatibility shim (small-N debugging)."""
        batch = TxArrays.from_txs([tx], self.fns)
        batch.sender_id = np.array([self.sender_id(tx.sender)], np.int32)
        return self.submit_arrays(batch)

    # -- provenance (receipts) -------------------------------------------------
    def block_of(self, tx_index: int) -> Optional[BlockStats]:
        """The block that confirmed arrival index ``tx_index`` (None while
        unconfirmed).  O(log blocks) against a cached stop array."""
        if tx_index >= self._ptr:
            return None
        if self._block_stops.shape[0] != len(self.blocks):
            self._block_stops = np.array([b.stop for b in self.blocks],
                                         np.int64)
        h = int(np.searchsorted(self._block_stops, tx_index, side="right"))
        blk = self.blocks[h]
        assert blk.start <= tx_index < blk.stop
        return blk

    def confirm_time_of(self, tx_index: int) -> Optional[float]:
        if tx_index >= self._ptr:
            return None
        return float(self._confirm[tx_index])

    def quorum(self, approvals: int) -> bool:
        return 3 * approvals >= 2 * self.n_validators

    def _grow(self, need: int):
        cap = self._t.shape[0]
        if self._n + need <= cap:
            return
        new_cap = max(1024, self._n + need, 2 * cap)

        def grow(a, dtype):
            out = np.empty(new_cap, dtype)
            out[: self._n] = a[: self._n]
            return out
        self._t = grow(self._t, np.float64)
        self._g = grow(self._g, np.int64)
        self._f = grow(self._f, np.int32)
        self._s = grow(self._s, np.int32)
        self._confirm = grow(self._confirm, np.float64)
        self._tmax = grow(self._tmax, np.float64)
        self._gcum = grow(self._gcum, np.int64)

    def _consolidate(self):
        if not self._staged:
            return
        new, m = self._staged, self._staged_n
        self._staged, self._staged_n = [], 0
        self._grow(m)
        lo, hi = self._n, self._n + m
        at = lo
        for b in new:
            k = len(b)
            self._t[at:at + k] = b.submit_time
            self._g[at:at + k] = b.gas
            self._f[at:at + k] = b.fn_id
            self._s[at:at + k] = b.sender_id
            at += k
        self._confirm[lo:hi] = np.nan
        # extend the running max (head-of-line eligibility) and gas cumsum
        # (packing) over the new tail only — amortized O(new txs)
        tmax_tail = np.maximum.accumulate(self._t[lo:hi])
        if lo:
            np.maximum(tmax_tail, self._tmax[lo - 1], out=tmax_tail)
        self._tmax[lo:hi] = tmax_tail
        self._gcum[lo:hi] = (np.cumsum(self._g[lo:hi])
                             + (self._gcum[lo - 1] if lo else 0))
        self._n = hi

    # -- block production ------------------------------------------------------
    def produce_block(self, now: float) -> BlockStats:
        """Pack the next block at time ``now``.

        FIFO head-of-line semantics (identical to ``Chain.produce_block``):
        eligible txs are the longest mempool *prefix* whose running-max
        submit_time is <= now — ``searchsorted`` on the precomputed running
        max; the gas cap is then the longest prefix of that whose gas cumsum
        fits the block limit — ``searchsorted`` on the gas cumsum.  A stuck
        head tx (future-timestamped, or gas > block limit by itself) blocks
        the queue in both engines; that is the documented intent.
        """
        self._consolidate()
        ptr = self._ptr
        hi = int(np.searchsorted(self._tmax[: self._n], now, side="right"))
        hi = max(hi, ptr)
        base = int(self._gcum[ptr - 1]) if ptr > 0 else 0
        k = int(np.searchsorted(self._gcum[ptr:hi],
                                base + self.block_gas_limit, side="right"))
        stop = ptr + k
        gas_used = (int(self._gcum[stop - 1]) - base) if stop > ptr else 0
        if stop > ptr:
            self._confirm[ptr:stop] = now
            if self._batch_handlers or self._state_handlers:
                counts = np.bincount(self._f[ptr:stop],
                                     minlength=len(self.fns))
                view = TxArrays(self._t[ptr:stop], self._g[ptr:stop],
                                self._f[ptr:stop], self._s[ptr:stop],
                                self.fns)
                for fid, h in self._batch_handlers.items():
                    if fid < counts.shape[0] and counts[fid]:
                        h(self.state, int(counts[fid]), view)
                for fid, h in self._state_handlers.items():
                    if fid < counts.shape[0] and counts[fid]:
                        m = view.fn_id == fid
                        h(self.state_arrays,
                          TxArrays(view.submit_time[m], view.gas[m],
                                   view.fn_id[m], view.sender_id[m],
                                   self.fns))
        assert self.quorum(self.n_validators - self.n_validators // 3)
        blk = BlockStats(len(self.blocks), now, stop - ptr, gas_used,
                         ptr, stop, self.blocks[-1].block_hash)
        self.blocks.append(blk)
        self.total_gas += gas_used
        self._ptr = stop
        self.events.emit(BlockPacked, time=now, height=blk.height,
                         n_txs=blk.n_txs, gas_used=gas_used,
                         block_hash=blk.block_hash)
        self._emit("block_packed", {"height": blk.height, "n_txs": blk.n_txs,
                                    "gas_used": gas_used,
                                    "block_hash": blk.block_hash})
        return blk

    def run_until(self, t_end: float):
        t = self.blocks[-1].time
        while t < t_end:
            t += self.block_time
            self.produce_block(t)

    # -- metrics ---------------------------------------------------------------
    @property
    def n_confirmed(self) -> int:
        return self._ptr

    @property
    def n_submitted(self) -> int:
        return self._n + self._staged_n

    def confirm_times(self) -> np.ndarray:
        return self._confirm[: self._ptr]

    def load_metrics(self, send_rate: float,
                     duration: float) -> Dict[str, float]:
        """Fig. 4 metrics, numerically identical to the object path."""
        n_conf = self._ptr
        if n_conf == 0:
            return {"send_rate": send_rate, "throughput": 0.0, "latency": 0.0,
                    "confirmed": 0, "submitted": self.n_submitted}
        lat = float(np.mean(self._confirm[:n_conf] - self._t[:n_conf]))
        return {"send_rate": send_rate,
                "throughput": n_conf / duration,
                "latency": lat,
                "confirmed": n_conf,
                "submitted": self.n_submitted}


class VectorRollup(ProverFace, EventHooks):
    """Vectorized mirror of ``rollup.Rollup`` with a multi-lane sequencer.

    Transactions stripe round-robin across ``n_lanes`` lanes; each lane cuts
    FIFO batches of ``batch_size`` which all seal concurrently (commit gas +
    per-batch tx xor-roots computed in one vectorized pass), then ONE
    amortized verify/execute settles the whole session — zkSync-style proof
    aggregation, now across lanes as well as batches.  ``n_lanes=1``
    reproduces ``Rollup``'s gas_log exactly (tests/test_engine.py).
    """

    soa_native = True
    fused_capable = True

    def __init__(self, l1, batch_size: int = ROLLUP_BATCH,
                 gas_table: GasTable = DEFAULT_GAS,
                 prove_time: float = 0.9, per_tx_time: float = 0.14,
                 n_lanes: int = 1, agg_width: int = 1,
                 prover_capacity: int = 1, finalize: str = "eager",
                 prover: Optional[ProverPipeline] = None):
        assert n_lanes >= 1
        self.l1 = l1
        self.batch_size = batch_size
        self.gas_table = gas_table
        self.prove_time = prove_time
        self.per_tx_time = per_tx_time
        self.n_lanes = n_lanes
        # event-log adoption + settlement-pipeline wiring (ONE copy for
        # both rollup faces — see prover.ProverFace)
        self._init_prover_face(l1, gas_table, prove_time, agg_width,
                               prover_capacity, finalize, prover)
        # share the L1's registry when it has one (`or` would discard an
        # empty-but-present registry: FnRegistry defines __len__)
        l1_fns = getattr(l1, "fns", None)
        self.fns: FnRegistry = l1_fns if l1_fns is not None else FnRegistry()
        self._sender_ids: Dict[str, int] = {}
        self.gas_log: List[Dict[str, Any]] = []
        # LedgerBackend face: StateArrays handlers applied at seal time
        # over the sealed txs in ARRIVAL order (pre-lane-sort), fn-filtered
        self.state_arrays = None
        self._state_handlers: Dict[int, Callable] = {}
        self.batch_digests: List[int] = []      # per-batch tx xor-roots
        self.update_digest: int = int(MIX_SEED)  # merged-buffer digest
        self.n_batches = 0
        self._pending: List[TxArrays] = []
        self._pending_n = 0
        self._last_time = 0.0
        # tx->batch provenance: submission order IS seal order, so the
        # seq->batch map extends chunk-wise at each seal (receipts resolve
        # a sequence number to its global batch id via batch_of_seq)
        self._next_seq = 0
        self._sealed_seq = 0
        self._prov_starts: List[int] = []        # chunk start seq per seal
        self._prov_batches: List[np.ndarray] = []  # per-tx global batch ids
        # per-batch L1 settlement refs: commit tx + (verify, execute) txs —
        # arrival indices on a VectorChain L1, Tx objects on an object L1
        self.batch_commit_ref: Dict[int, Any] = {}
        self.batch_settle_ref: Dict[int, Any] = {}
        self._init_events()

    # -- sequencing ------------------------------------------------------------
    def submit_arrays(self, batch: TxArrays):
        """Queue a SoA batch; returns the ``[lo, hi)`` sequence-number
        range assigned to it (this rollup's tx provenance namespace)."""
        if batch.fns is not self.fns:
            remap = np.array([self.fns.id(n) for n in batch.fns.names],
                             np.int32)
            batch = TxArrays(batch.submit_time, batch.gas,
                             remap[batch.fn_id] if len(batch) else
                             batch.fn_id, batch.sender_id, self.fns)
        lo = self._next_seq
        self._pending.append(batch)
        self._pending_n += len(batch)
        self._next_seq += len(batch)
        return lo, lo + len(batch)

    def sender_id(self, sender: str) -> int:
        """Stable sender-name -> id mapping for this rollup's SoA stream
        (same contract as VectorChain.sender_id; batched emitters must use
        the TARGET's namespace so ids stay consistent within one stream)."""
        return self._sender_ids.setdefault(sender, len(self._sender_ids))

    def register_state(self, fn: str, handler: Callable):
        """StateArrays handler (LedgerBackend): handler(state_arrays, view)
        with ``view`` holding only ``fn``'s sealed txs, arrival order."""
        if self.state_arrays is None:
            from repro.core.state import StateArrays
            self.state_arrays = StateArrays()
            self.state_arrays.enable_dirty_tracking()
        self._state_handlers[self.fns.id(fn)] = handler

    def state_root(self) -> str:
        return self.state_arrays.root() if self.state_arrays is not None \
            else ""

    def _apply_state(self, txs: "TxArrays"):
        for fid, h in self._state_handlers.items():
            m = txs.fn_id == fid
            if m.any():
                h(self.state_arrays,
                  TxArrays(txs.submit_time[m], txs.gas[m], txs.fn_id[m],
                           txs.sender_id[m], self.fns))

    def submit(self, tx):
        """Object-Tx compatibility shim."""
        batch = TxArrays.from_txs([tx], self.fns)
        batch.sender_id = np.array([self.sender_id(tx.sender)], np.int32)
        return self.submit_arrays(batch)

    def batch_of_seq(self, seq: int) -> Optional[int]:
        """Global batch id that sealed sequence number ``seq`` (None while
        still pending).  Chunk-indexed: one bisect over seal chunks."""
        if seq >= self._sealed_seq or seq < 0:
            return None
        import bisect
        c = bisect.bisect_right(self._prov_starts, seq) - 1
        return int(self._prov_batches[c][seq - self._prov_starts[c]])

    def _commit_gas_vectors(self):
        from repro.core.gas import commit_gas_vectors
        return commit_gas_vectors(self.fns.names, self.gas_table)

    def seal(self) -> int:
        """Seal every pending tx into lane batches; returns #batches sealed.

        One vectorized pass computes, for all batches at once: per-batch
        (fn -> count) histograms (commit gas), per-batch max submit_time
        (the L1 commit timestamp), and per-batch xor-roots; the merged word
        buffer of the whole seal is folded through the rollup_digest kernel
        path (Pallas on TPU, bit-exact NumPy mirror on CPU).  Sealed
        batches enqueue proof jobs on the prover pipeline; settlement
        (verify/execute) happens there (core/prover.py).
        """
        if not self._pending:
            self._emit_window(0)
            return 0
        txs = (self._pending[0] if len(self._pending) == 1 else
               TxArrays(np.concatenate([b.submit_time for b in self._pending]),
                        np.concatenate([b.gas for b in self._pending]),
                        np.concatenate([b.fn_id for b in self._pending]),
                        np.concatenate([b.sender_id for b in self._pending]),
                        self.fns))
        self._pending, self._pending_n = [], 0
        if self._state_handlers:
            # execute against the SoA account state in arrival order —
            # shard/lane layout must not change the committed state
            self._apply_state(txs)
        n = len(txs)
        idx = np.arange(n)
        lane = idx % self.n_lanes
        pos = idx // self.n_lanes                 # FIFO position within lane
        batch_in_lane = pos // self.batch_size
        # order (lane-major, FIFO within lane) so batches are contiguous
        order = np.lexsort((pos, lane))
        lane_o, bil_o = lane[order], batch_in_lane[order]
        # compact global batch ids in (lane, batch_in_lane) order
        seg_new = np.empty(n, bool)
        seg_new[0] = True
        seg_new[1:] = (lane_o[1:] != lane_o[:-1]) | (bil_o[1:] != bil_o[:-1])
        batch_id = np.cumsum(seg_new) - 1
        nb = int(batch_id[-1]) + 1
        starts = np.flatnonzero(seg_new)

        fn_o = txs.fn_id[order]
        t_o = txs.submit_time[order]
        counts = np.zeros((nb, len(self.fns)), np.int64)
        np.add.at(counts, (batch_id, fn_o), 1)
        base, percall = self._commit_gas_vectors()
        commit = (counts > 0) @ base + counts @ percall
        n_txs = counts.sum(axis=1)
        now = np.maximum.reduceat(t_o, starts)
        # per-batch xor-roots over the interleaved word buffer (the same
        # fold family as xor_fold_digest, segmented per batch)
        words = TxArrays(t_o, txs.gas[order], fn_o, txs.sender_id[order],
                         self.fns).word_buffer()
        roots = xor_fold_digest_segments(words, starts * 4)
        self.batch_digests.extend(int(r) for r in roots)
        # merged update-buffer digest through the kernel path
        self.update_digest = update_buffer_digest(words)

        first = self.n_batches
        # tx->batch provenance: map each sealed tx (arrival order == seq
        # order) to its global batch id, extending the seq->batch chunks
        arrival_batch = np.empty(n, np.int64)
        arrival_batch[order] = first + batch_id
        self._prov_starts.append(self._sealed_seq)
        self._prov_batches.append(arrival_batch)
        self._sealed_seq += n

        # L1 commits: one tx per batch, Table-I-calibrated gas.  Lanes can
        # finish out of global time order; post commits time-sorted so the
        # L1's FIFO head-of-line rule never stalls on a later lane's commit
        # (stable sort -> no-op for n_lanes=1, preserving Rollup parity).
        post = np.argsort(now, kind="stable")
        commit_batch = TxArrays(
            now[post].astype(np.float64), commit[post].astype(np.int64),
            np.full(nb, self.fns.id("rollup_commit"), np.int32),
            np.zeros(nb, np.int32), self.fns)
        refs = self._l1_submit(commit_batch)
        inv_post = np.empty(nb, np.int64)
        inv_post[post] = np.arange(nb)
        rows = []
        for j in range(nb):
            self.batch_commit_ref[first + j] = refs[int(inv_post[j])]
            rows.append({
                "batch": first + j, "lane": int(lane_o[starts[j]]),
                "n_txs": int(n_txs[j]), "commit": int(commit[j]),
                "verify": 0, "execute": 0, "total": int(commit[j])})
        self.gas_log.extend(rows)
        self.n_batches += nb
        self._last_time = float(now.max())
        self.prover.enqueue(self, first, roots, n_txs, now, rows)
        self.events.emit(BatchSealed, time=self._last_time,
                         shard=self._event_shard, first_batch=first,
                         n_batches=nb, n_txs=n, digest=self.update_digest)
        self._emit("batch_sealed", {
            "first_batch": first, "n_batches": nb, "n_txs": n,
            "digest": self.update_digest})
        self._emit_window(nb)
        return nb

    def _l1_submit(self, batch: TxArrays) -> List[Any]:
        """Submit to the L1; returns one settlement ref per tx — the L1
        arrival index on a VectorChain, the submitted Tx on an object
        Chain (both resolve to a block through the NodeClient)."""
        if getattr(self.l1, "soa_native", False):
            lo, hi = self.l1.submit_arrays(batch)
            return list(range(lo, hi))
        from repro.core.ledger import Tx                # object Chain
        txs = [Tx(batch.fns.names[batch.fn_id[i]], "sequencer", {},
                  int(batch.gas[i]), float(batch.submit_time[i]))
               for i in range(len(batch))]
        for tx in txs:
            self.l1.submit(tx)
        return txs

    # -- settlement (routed through the shared prover pipeline) -----------------
    def flush(self):
        self.seal()
        self.settle_session()
        self.prover.drain(self)

    def _post_settlement(self, verify: int, execute: int, at: float,
                         n_batches: int):
        """Prover callback: post one verify + execute pair to the L1."""
        settle = TxArrays(
            np.full(2, at),
            np.array([verify, execute], np.int64),
            np.array([self.fns.id("rollup_verify"),
                      self.fns.id("rollup_execute")], np.int32),
            np.zeros(2, np.int32), self.fns)
        return tuple(self._l1_submit(settle))

    # -- metrics ---------------------------------------------------------------
    def throughput(self, l1_tps: float) -> float:
        """Paper's method, scaled by concurrent lanes."""
        return self.n_lanes * self.batch_size * l1_tps

    def latency(self, n_calls: int) -> float:
        """Table-II latency model (prover.session_latency — ONE formula
        shared with the object face); lanes sequence concurrently, so
        the session latency is the slowest lane's (ceil-split) share."""
        return session_latency(n_calls, batch_size=self.batch_size,
                               prove_time=self.prove_time,
                               per_tx_time=self.per_tx_time,
                               n_lanes=self.n_lanes,
                               capacity=self.prover.capacity)
