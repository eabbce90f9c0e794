"""FL task lifecycle smart contracts (TSC): publishTask (paper Algo. 1),
selectTrainers, submitLocalModel (Algo. 2) — executed against the chain or
rollup state dict, with role checks (ASC) and escrow hooks (DSC)."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np

from repro.core.escrow import Escrow
from repro.core.ledger import AccessControl, Tx
from repro.core.storage import BlobStore


@dataclasses.dataclass
class Task:
    task_id: str
    model_cid: str          # IPFS-style content id of the model architecture
    description_cid: str
    publisher: str
    rounds_total: int
    required_accuracy: float
    reward: float
    trainers: List[str] = dataclasses.field(default_factory=list)
    current_round: int = 0
    state: str = "selection"     # selection -> training -> evaluated -> closed
    # per-round: {round: {trainer: model_cid}}
    models: Dict[int, Dict[str, str]] = dataclasses.field(default_factory=dict)
    scores: Dict[str, float] = dataclasses.field(default_factory=dict)


class TaskContract:
    """TSC bound to an access controller, escrow and blob store."""

    def __init__(self, acl: AccessControl, escrow: Escrow, store: BlobStore):
        self.acl = acl
        self.escrow = escrow
        self.store = store
        self.tasks: Dict[str, Task] = {}

    # Algo. 1 -------------------------------------------------------------------
    def publish_task(self, sender: str, task_id: str, model_cid: str,
                     description_cid: str, rounds_total: int,
                     required_accuracy: float, reward: float) -> Task:
        assert self.acl.has_role(sender, "task_publisher"), \
            "isTaskPublisher(msg.sender) failed"
        assert task_id not in self.tasks, "duplicate taskId"
        # false-reporting guard: reward locked up-front in the DSC
        self.escrow.deposit(sender, task_id, reward)
        task = Task(task_id, model_cid, description_cid, sender,
                    rounds_total, required_accuracy, reward)
        self.tasks[task_id] = task
        return task

    # trainer selection (reputation-ranked, on-chain) -----------------------------
    def select_trainers(self, task_id: str, reputations,
                        n_select: int, min_rep: float = 0.0,
                        trainer_ids: Optional[List[str]] = None) -> List[str]:
        """Rank trainers by reputation; ties break by stable trainer index
        (dict insertion / array position), never by id-string order.

        ``reputations`` is either {trainer_id: rep} or an array aligned with
        ``trainer_ids`` — the array form is the scheduler hot path (the
        reputation book is already a vector; no dict roundtrip).
        """
        task = self.tasks[task_id]
        assert task.state == "selection"
        if isinstance(reputations, dict):
            assert trainer_ids is None, "trainer_ids implied by the dict"
            trainer_ids = list(reputations)
            reps = np.asarray(list(reputations.values()), np.float64)
        else:
            reps = np.asarray(reputations, np.float64)
            assert trainer_ids is not None and len(trainer_ids) == len(reps)
        ok = np.array([self.acl.has_role(t, "trainer")
                       for t in trainer_ids], bool) & (reps >= min_rep)
        idx = np.flatnonzero(ok)
        # stable sort on -rep: equal reputations keep ascending index order
        order = idx[np.argsort(-reps[idx], kind="stable")]
        task.trainers = [trainer_ids[i] for i in order[:n_select]]
        task.state = "training"
        return task.trainers

    # Algo. 2 --------------------------------------------------------------------
    def submit_local_model(self, sender: str, task_id: str, round_: int,
                           local_model_cid: str):
        task = self.tasks[task_id]
        assert sender in task.trainers, "isTrainerInTask failed"
        assert task.state == "training"
        assert self.store.has(local_model_cid), "model blob not on IPFS"
        task.models.setdefault(round_, {})[sender] = local_model_cid

    def submitted(self, task_id: str, round_: int, trainer: str) -> bool:
        return trainer in self.tasks[task_id].models.get(round_, {})

    def advance_round(self, task_id: str):
        task = self.tasks[task_id]
        task.current_round += 1
        if task.current_round >= task.rounds_total:
            task.state = "evaluated"

    def retire_models(self, task_id: str) -> List[str]:
        """Forget a closed task's per-round model submissions; returns
        their content ids, for the caller to unpin from the blob store."""
        task = self.tasks[task_id]
        assert task.state == "closed", "retire_models before close_task"
        cids = sorted({c for per in task.models.values()
                       for c in per.values()})
        task.models.clear()
        return cids

    def record_scores(self, task_id: str, scores: Dict[str, float]):
        task = self.tasks[task_id]
        task.scores.update(scores)

    def close_task(self, task_id: str) -> Dict[str, float]:
        """Settle rewards proportionally to final scores (free-riding guard:
        zero-score trainers get nothing; their collateral is slashed)."""
        task = self.tasks[task_id]
        assert task.state == "evaluated"
        payouts = self.escrow.settle(task.task_id, task.scores)
        task.state = "closed"
        return payouts

    # chain-handler adapters (state-dict form used by Chain/Rollup) --------------
    @staticmethod
    def handler_publish(state: Dict[str, Any], tx: Tx):
        state.setdefault("tasks", {})[tx.payload.get("taskId", tx.tx_id)] = {
            "publisher": tx.sender, "state": "selection", "round": 0}

    @staticmethod
    def handler_submit(state: Dict[str, Any], tx: Tx):
        t = state.setdefault("models", {})
        key = (tx.payload.get("taskId", "t0"), tx.payload.get("round", 0))
        t.setdefault(str(key), {})[tx.sender] = tx.payload.get("cid", "")

    @staticmethod
    def handler_obj_rep(state: Dict[str, Any], tx: Tx):
        state.setdefault("o_rep", {})[tx.sender] = tx.payload.get("value", 0.0)

    @staticmethod
    def handler_subj_rep(state: Dict[str, Any], tx: Tx):
        state.setdefault("s_rep", {})[tx.sender] = tx.payload.get("value", 0.0)

    # batched adapters (vector engine, engine.VectorChain.register_batch):
    # one call per (block, fn) updating aggregate counters from the SoA view
    # instead of one Python call per tx.
    @staticmethod
    def batch_counter(fn: str):
        """Handler counting confirmed calls of ``fn`` per fn and per sender."""

        def handler(state: Dict[str, Any], n: int, view) -> None:
            calls = state.setdefault("calls", {})
            calls[fn] = calls.get(fn, 0) + n
            fid = view.fns.id(fn)
            senders = view.sender_id[view.fn_id == fid]
            per = state.setdefault("calls_by_sender", {}).setdefault(fn, {})
            for sid, cnt in zip(*np.unique(senders, return_counts=True)):
                per[int(sid)] = per.get(int(sid), 0) + int(cnt)
        return handler

    @classmethod
    def register_batch_handlers(cls, chain, fns=None) -> None:
        """Wire counting adapters for the Table-I functions (or ``fns``)
        onto a VectorChain."""
        from repro.core.gas import FUNCTIONS
        for fn in (fns or FUNCTIONS):
            chain.register_batch(fn, cls.batch_counter(fn))
