"""zk-Rollup Layer-2 engine (paper §III-C.3) — and its TPU-native analogue.

Two faces of the same idea ("don't pay the expensive global medium per
transaction; batch locally, post one verified summary"):

1. **Chain face** (`Rollup`): batches FL transactions off-chain, executes
   them against the L2 state, produces a validity digest (stand-in for the
   zk proof — see DESIGN.md security note), and posts commit/verify/execute
   to the L1 chain with Table-I-calibrated gas.  Reproduces the paper's
   20x gas reduction and >3000 TPS.

2. **Mesh face** (`rollup_round`, fl/round.py): H local optimizer steps
   accumulate on-device ("off-chain"), then ONE reputation-weighted
   all-reduce (Eq. 1) + digest crosses the pod interconnect ("commit").
   Collective bytes drop ~H-fold — the gas story, re-materialised on ICI.

At scale the single sequencer saturates; the **sharded rollup fabric**
(core/shards.py `ShardedRollup`) runs K `VectorRollup` shards — each with
its own sequencer lanes and its own partition of the array-native account
state (core/state.py `StateArrays`) — all settling to ONE shared L1.  At
window boundaries each shard's partition root is merged into a *fabric
root* committing the whole fleet's state; the flat array state root itself
is shard-count invariant, so the same transactions commit to the same
state no matter how they were sharded.

Security caveat: every root in this simulator — `state_digest`, the batch
`word_digest`, the chunked `StateArrays` root and the fabric root — is a
validity *stand-in*, not a zk proof.  The digests are deterministic and
tamper-evident (replaying the batch from `pre_root` must reach
`post_root`), which is the soundness condition a zk-SNARK would prove
succinctly; no cryptographic succinctness or zero-knowledge property is
claimed.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable, Dict, List, Optional

from repro.core.events import BatchSealed
from repro.core.gas import DEFAULT_GAS, ROLLUP_BATCH, GasTable
from repro.core.ledger import Chain, EventHooks, ObjectLedgerFace, Tx
from repro.core.prover import ProverFace, ProverPipeline, session_latency
from repro.core.state import canonical_bytes


def state_digest(state: Dict[str, Any]) -> str:
    """Deterministic state-root stand-in (content hash of the L2 state).

    Built on ``core.state.canonical_bytes``: the old
    ``json.dumps(..., default=repr)`` fallback truncated ndarray reprs
    (two different 2000-element arrays share a repr, hence shared a
    digest) and collapsed dataclasses to their repr; the canonical
    encoding is total, type-tagged and collision-resistant
    (tests/test_state.py pins the regression)."""
    return hashlib.sha256(canonical_bytes(state)).hexdigest()[:32]


@dataclasses.dataclass
class BatchProof:
    batch_id: int
    n_txs: int
    pre_root: str
    post_root: str
    tx_root: str
    # xor-mix fold over the batch's transaction words — same construction
    # the Pallas rollup_digest kernel computes over merged update buffers
    # (kernels.digest.xor_fold_digest is the bit-exact CPU mirror)
    word_digest: int = 0

    def verify(self, pre_state: Dict[str, Any],
               replay: Callable[[Dict[str, Any]], Dict[str, Any]]) -> bool:
        """Validity check: replaying the batch from pre_root reaches
        post_root.  (A zk-SNARK proves this without replay; the simulator
        replays — same soundness condition, no cryptographic claim.)"""
        if state_digest(pre_state) != self.pre_root:
            return False
        return state_digest(replay(pre_state)) == self.post_root


class Rollup(ObjectLedgerFace, ProverFace, EventHooks):
    """L2 sequencer + prover + L1 settlement."""

    def __init__(self, l1: Chain, batch_size: int = ROLLUP_BATCH,
                 gas_table: GasTable = DEFAULT_GAS,
                 prove_time: float = 0.9, per_tx_time: float = 0.14,
                 agg_width: int = 1, prover_capacity: int = 1,
                 finalize: str = "eager",
                 prover: Optional[ProverPipeline] = None):
        self.l1 = l1
        self.batch_size = batch_size
        self.gas_table = gas_table
        self.prove_time = prove_time      # per-batch prover latency (s)
        self.per_tx_time = per_tx_time    # sequencer execution latency (s)
        self.state: Dict[str, Any] = {}
        self._handlers: Dict[str, Callable] = {}
        # LedgerBackend face: sender namespace, SoA-lowering adapter and
        # StateArrays handler plumbing shared with Chain (one copy of the
        # id-pinning invariant — see ledger.ObjectLedgerFace)
        self._init_object_face()
        self.pending: List[Tx] = []
        self.batches: List[BatchProof] = []
        self.gas_log: List[Dict[str, Any]] = []
        self._sealing = False
        self._last_time = 0.0
        # tx->batch provenance + per-batch L1 refs (receipts): mirrors
        # engine.VectorRollup's maps, keyed by tx_id on the object path
        self.tx_batch: Dict[str, int] = {}
        self.batch_commit_ref: Dict[int, Tx] = {}
        self.batch_settle_ref: Dict[int, tuple] = {}
        self._init_events()
        # event-log adoption + settlement-pipeline wiring (ONE copy for
        # both rollup faces — see prover.ProverFace; the verify/execute
        # bookkeeping that used to live here as _settle_session is the
        # pipeline's now)
        self._init_prover_face(l1, gas_table, prove_time, agg_width,
                               prover_capacity, finalize, prover)

    def register(self, fn: str, handler: Callable):
        self._handlers[fn] = handler

    # -- sequencing -------------------------------------------------------------
    def submit(self, tx: Tx):
        self.pending.append(tx)
        if len(self.pending) >= self.batch_size:
            self.seal_batch()

    def _execute(self, state: Dict[str, Any], txs: List[Tx]) -> Dict[str, Any]:
        # PURE (state, txs) -> state replay: BatchProof.verify's soundness
        # story replays batches through this function, so it must not
        # touch the live StateArrays (those handlers run in seal_batch)
        for tx in txs:
            handler = self._handlers.get(tx.fn)
            if handler is not None:
                handler(state, tx)
        return state

    def seal(self) -> int:
        """Seal every pending tx (LedgerBackend face shared with
        VectorRollup.seal / ShardedRollup.seal); returns #batches."""
        nb = 0
        while self.pending:
            if self.seal_batch() is None:
                break
            nb += 1
        self._emit_window(nb)
        return nb

    def seal_batch(self) -> Optional[BatchProof]:
        if not self.pending or self._sealing:
            # re-entrancy guard: a handler that submits back into the rollup
            # during _execute must not trigger a nested seal against a
            # half-executed state; the queued txs seal on the next
            # seal_batch/flush instead.
            return None
        self._sealing = True
        try:
            txs, self.pending = self.pending[: self.batch_size], \
                self.pending[self.batch_size:]
            pre_root = state_digest(self.state)
            self.state = self._execute(self.state, txs)
            if self._state_handlers:
                # SoA state handlers run at seal time, OUTSIDE the pure
                # replay function (1-row views, same handler code as the
                # vector/sharded faces)
                for tx in txs:
                    self._apply_state_tx(tx)
            post_root = state_digest(self.state)
            tx_root = hashlib.sha256(
                "".join(t.tx_id for t in txs).encode()).hexdigest()[:32]
            proof = BatchProof(len(self.batches), len(txs), pre_root,
                               post_root, tx_root,
                               word_digest=self._word_digest(txs))
            self.batches.append(proof)
            for t in txs:
                self.tx_batch[t.tx_id] = proof.batch_id
            row = self._settle(proof, txs)
            # one proof job per sealed batch (settlement lives in the
            # pipeline; see core/prover.py)
            self.prover.enqueue(self, proof.batch_id, [proof.word_digest],
                                [proof.n_txs], [self._last_time], [row])
            self.events.emit(BatchSealed, time=self._last_time,
                             shard=self._event_shard,
                             first_batch=proof.batch_id, n_batches=1,
                             n_txs=proof.n_txs, digest=proof.word_digest)
            self._emit("batch_sealed", {
                "first_batch": proof.batch_id, "n_batches": 1,
                "n_txs": proof.n_txs, "digest": proof.word_digest})
        finally:
            self._sealing = False
        return proof

    @staticmethod
    def _word_digest(txs: List[Tx]) -> int:
        """Batched digest over the merged tx-word buffer — the same
        xor-mix fold the Pallas rollup_digest kernel computes (see
        kernels.digest.xor_fold_digest, the mirror pinned against it)."""
        from repro.core.engine import TxArrays
        from repro.kernels.digest import xor_fold_digest
        return xor_fold_digest(TxArrays.from_txs(txs).word_buffer())

    def flush(self):
        if self._sealing:
            # re-entrant flush from a handler: the outer seal/flush in
            # progress will drain pending and settle the session; settling
            # here would split the session in two (double verify/execute)
            # with the settlement timestamped before the outer commit.
            return
        self.seal()
        self.settle_session()
        self.prover.drain(self)

    # -- L1 settlement: commit per batch; verify+execute once per aggregate
    # (zkSync-style proof aggregation — matches Table I, where Verify and
    # Execute stay ~constant even at 5 batches) ---------------------------------
    def _settle(self, proof: BatchProof, txs: List[Tx]) -> Dict[str, Any]:
        by_fn: Dict[str, int] = {}
        for t in txs:
            by_fn[t.fn] = by_fn.get(t.fn, 0) + 1
        commit = sum(
            self.gas_table.commit_base.get(fn, 37000)
            + n * self.gas_table.commit_per_call.get(fn, 500)
            for fn, n in by_fn.items())
        now = max((t.submit_time for t in txs), default=0.0)
        commit_tx = Tx("rollup_commit", "sequencer",
                       {"batch": proof.batch_id,
                        "root": proof.post_root}, commit, now)
        self.l1.submit(commit_tx)
        self.batch_commit_ref[proof.batch_id] = commit_tx
        row = {"batch": proof.batch_id, "n_txs": proof.n_txs,
               "commit": commit, "verify": 0, "execute": 0,
               "total": commit}
        self.gas_log.append(row)
        self._last_time = now
        return row

    def _post_settlement(self, verify: int, execute: int, at: float,
                         n_batches: int):
        """Prover callback: post one verify + execute pair to the L1."""
        refs = []
        for phase, gas in (("verify", verify), ("execute", execute)):
            settle_tx = Tx(f"rollup_{phase}", "sequencer",
                           {"batches": n_batches}, gas, at)
            self.l1.submit(settle_tx)
            refs.append(settle_tx)
        return tuple(refs)

    # -- metrics ---------------------------------------------------------------
    def throughput(self, l1_tps: float) -> float:
        """Paper's method: L2 TPS = batch_size x L1 TPS."""
        return self.batch_size * l1_tps

    def latency(self, n_calls: int) -> float:
        """End-to-end L2 latency model calibrated to Table II
        (prover.session_latency — ONE formula shared with the vector
        face, so identical specs model identical prove/settle timing)."""
        return session_latency(n_calls, batch_size=self.batch_size,
                               prove_time=self.prove_time,
                               per_tx_time=self.per_tx_time,
                               capacity=self.prover.capacity)
