"""FL driver: the paper's FL task through the node's normal path.

Entry point family: ``repro.fl.server.AutoDFL`` over the configuration's
``NodeSpec`` (the node's L1/L2 defaults), ``CohortKernels`` shared by one
``VectorCohort`` per task, and ``Scheduler(node, seal_every=1)`` with the
fused window loop and the cross-task megastep left to choose themselves
(``"auto"``).  A unit of work is one epoch: ``Scheduler.run`` over the
mix's tasks (select, the rounds, settle, flush, the fused loop's
``execute()``).  It counts the epoch's Table-I transactions once they are
settled on the L1: every receipt finalized and every L1 transaction of
the epoch in a block.  So the end-to-end "window" of this cell is one
epoch.

Set-up draws the data (``generators/fl_data.py``), puts the trainers'
partitions and the validation set on the chip, compiles the L1 packer at
every mempool bucket the run can reach, and runs warm-up epochs until
``stable_epochs`` in a row compile nothing.

The timed path keeps, per epoch, what the protocol published (its
transaction counts, rollup gas rows and sequence numbers, and for the
epochs ``check`` replays the tasks' round records); ``check`` compares
them with ``reference/fl.py``.  ``cfg["fault"]`` (control runs and tests
only, never in ``BENCHMARK.json``) breaks one guarantee: ``bf16_state``,
``no_dp`` and ``unsettled_tx`` change the program's run; ``drop_update``,
``uniform_merge`` and ``malicious_as_good`` alter its records, as a
program with that fault would have written them.
"""
from __future__ import annotations

import collections
import json
import math
import time
from typing import Dict, List, Tuple

import numpy as np

from harness.traffic import rng_for

#: receipts read back at this many seeded transactions
RECEIPT_SAMPLES = 256
#: epochs (besides the last) replayed round by round
REPLAY_EPOCHS = 2
RECORD_FAULTS = ("drop_update", "uniform_merge", "malicious_as_good")


class _Bf16Model:
    """The model with its parameters held in bf16 (the ``bf16_state``
    fault)."""

    def __init__(self, model):
        self._m = model
        self.cfg = model.cfg

    def init_params(self, key):
        import jax
        import jax.numpy as jnp
        return jax.tree.map(lambda l: l.astype(jnp.bfloat16),
                            self._m.init_params(key))

    def loss(self, params, batch):
        return self._m.loss(params, _bf16_images(batch))

    def forward(self, params, batch):
        return self._m.forward(params, _bf16_images(batch))


def _bf16_images(batch):
    import jax.numpy as jnp
    return {**batch, "images": batch["images"].astype(jnp.bfloat16)}


class Driver:
    RATE_METRIC = "ledger_tx_per_s"
    TAIL_METRIC = "ledger_window_p95_ms"

    def __init__(self, cfg: Dict, mix: Dict, seed: int, seconds: float,
                 devices, registry):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.seconds = seconds
        self.devices = devices
        self.registry = registry
        self.ref_mod = registry.module("reference", cfg["reference"])
        self.gen = registry.module("generators", mix["generator"])
        self.gas = json.loads(
            (registry.dir / "reference" / "table1_gas.json").read_text())
        self.fault = cfg.get("fault")
        self.n = int(cfg["n_trainers"])
        self.k = int(cfg["n_select"])
        self.tasks = int(mix["tasks_per_epoch"])
        self.rounds = int(mix["rounds"])
        self.steps = int(mix["local_steps"])
        self.batch = int(mix["batch"])
        self.epoch = 0
        self.first_measured = 0
        self.log: List[Dict] = []          # per epoch: what it published
        self.kept: Dict[int, Dict] = {}    # epoch -> round records
        self._rounds: Dict[str, List] = {}  # this epoch's, by task id
        self.unsettled = collections.deque()
        self.replay_at: List[int] = []     # measured epochs check replays
        self.measuring = False
        self._obs0 = None                  # counters at the window's start

    # -- set-up ---------------------------------------------------------------
    def _spec(self):
        from repro.api import ChainSpec, NodeSpec, RollupSpec
        n = self.cfg["node"]
        return NodeSpec(
            chain=ChainSpec(n_validators=int(n["n_validators"]),
                            block_time=float(n["block_time_s"]),
                            block_gas_limit=int(n["block_gas_limit"])),
            rollup=RollupSpec(batch_size=int(n["batch_size"]),
                              n_lanes=int(n["n_lanes"])),
            trainer_funds=float(n["trainer_funds"]),
            publisher_funds=float(n["publisher_funds"]))

    def setup(self) -> None:
        import jax

        from repro.configs.registry import get_config
        from repro.fl.cohort import CohortKernels
        from repro.fl.dp import DPConfig
        # a program without the round records the check replays cannot
        # run this cell: the import fails here, before any work
        from repro.fl.scheduler import RoundRecord, Scheduler  # noqa: F401
        from repro.fl.server import AutoDFL
        from repro.models import lenet
        from repro.models.model import build_model
        from repro.optim.optimizers import OptimizerSpec, make_optimizer

        d = self.cfg["data"]
        self.data = self.gen.generate(
            self.mix, self.seed, self.registry, n_trainers=self.n,
            per=int(d["images_per_trainer"]), n_val=int(d["validation_images"]))
        dev = self.devices[0]
        self.train_x = jax.device_put(self.data.train_x, dev)
        self.train_y = jax.device_put(self.data.train_y, dev)
        val = {"images": jax.device_put(self.data.val_x, dev),
               "labels": jax.device_put(self.data.val_y, dev)}
        mcfg = get_config(self.cfg["model"]["name"])
        model = build_model(mcfg)
        o = self.mix["optimizer"]
        bf16 = self.fault == "bf16_state"
        if bf16:
            model = _Bf16Model(model)
        self.opt = make_optimizer(OptimizerSpec(
            name=o["name"], lr=float(o["lr"]), beta1=float(o["beta1"]),
            grad_clip=float(o["grad_clip"]),
            moment_dtype="bfloat16" if bf16
            else self.cfg["precision"]["momentum"]))
        self.model = model
        eval_fn = jax.jit(lambda p, b: lenet.accuracy(mcfg, p, b))
        if bf16:
            eval_fn = jax.jit(lambda p, b: lenet.accuracy(
                mcfg, p, _bf16_images(b)))
        node = AutoDFL(model, self.opt, self.n, eval_fn, val,
                       spec=self._spec())
        if node.don.n_oracles != int(d["n_oracles"]):
            raise ValueError("the node's DON has another oracle count")
        node.chain.events.cap = int(self.cfg["node"]["event_cap"])
        self.node = node
        self.client = node.client()
        dp = self.mix["dp"]
        self.dp = DPConfig(enabled=self.fault != "no_dp",
                           noise_multiplier=float(dp["noise_multiplier"]),
                           clip_norm=float(dp["clip_norm"]),
                           batch_size=self.batch)
        self.kernels = CohortKernels(model, self.opt, self.dp)
        self.sch = Scheduler(node, seal_every=1, on_round=self._on_round)
        self.behaviors = [self.mix["behaviors"][i % len(self.mix["behaviors"])]
                          for i in range(self.n)]

        self._take = jax.jit(_take)
        rng = rng_for(self.seed, stream=2)
        replay = rng.choice(int(self.mix["replay_window"]), REPLAY_EPOCHS,
                            replace=False)
        self._warm_pack()
        self._warm_up()
        self.first_measured = self.epoch
        self.replay_at = sorted(self.first_measured + int(e) for e in replay)
        self.measuring = True

    def _warm_up(self) -> None:
        """Epochs until ``stable_epochs`` in a row compile nothing (at
        least ``min_epochs``, at most ``max_epochs``)."""
        import jax
        w = self.mix["warmup"]
        compiles = [0]

        def on(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles[0] += 1
        jax.monitoring.register_event_duration_secs_listener(on)
        quiet = 0
        for i in range(int(w["max_epochs"])):
            c0 = compiles[0]
            self.step()
            quiet = quiet + 1 if compiles[0] == c0 else 0
            if i + 1 >= int(w["min_epochs"]) and \
                    quiet >= int(w["stable_epochs"]):
                break

    def _warm_pack(self) -> None:
        """Compile the L1 block packer at every power-of-two mempool bucket
        the run can reach (it packs the whole L1 history each epoch)."""
        from repro.kernels.factory import get_kernel
        epochs = int(self.mix["warmup"]["max_epochs"]) + math.ceil(
            self.seconds * float(self.mix["max_epochs_per_s"])) + 1
        # L1 txs an epoch: a commit per batch of each window, and the
        # verify and execute; blocks an epoch: its modeled seconds (0.01 s
        # per protocol tx) plus the 5 s the scheduler runs past it
        bs = int(self.cfg["node"]["batch_size"])
        txs = self.tasks * (1 + 2 * self.k * self.rounds + self.k)
        l1_max = epochs * (txs // bs + self.rounds + 4)
        blocks = [16, 32, _pow2(int(0.01 * txs) + 8)]
        pack = get_kernel("block_pack")
        size = 16
        while size // 2 < max(l1_max, 16):
            tmax = np.arange(size, dtype=np.float64)
            gcum = np.arange(1, size + 1, dtype=np.int64)
            for b in sorted(set(blocks)):
                pack(tmax, gcum, np.arange(1, b + 1, dtype=np.float64),
                     np.full(b, size, np.int64),
                     int(self.cfg["node"]["block_gas_limit"]), 0)
            size *= 2

    # -- one epoch --------------------------------------------------------------
    def _batch_fn(self, epoch: int, task: int):
        def fn(sel, rnd):
            rows = self.gen.batch_rows(self.seed, epoch, task, rnd, self.n,
                                       self.steps, self.batch, self.data.per)
            return self._take(self.train_x, self.train_y,
                              rows[np.asarray(sel)])
        return fn

    def step(self) -> int:
        from repro import obs
        from repro.api import FLTaskSpec
        from repro.fl.cohort import VectorCohort
        node, e = self.node, self.epoch
        if self.measuring and self._obs0 is None:
            self._obs0 = obs.counters()
        rollup, chain = node.rollup, node.chain
        rep_before = np.asarray(node.book.reputation)
        book_before = node.book
        calls0 = dict(node.protocol_calls)
        seq0, g0, mega0 = rollup._next_seq, len(rollup.gas_log), \
            self.sch.mega_windows
        seeds = []
        self._rounds = {}
        for t in range(self.tasks):
            cohort_seed, init_seed = self.gen.task_seeds(self.seed, e, t)
            seeds.append(cohort_seed)
            cohort = VectorCohort(
                self.model, self.opt, self._batch_fn(e, t), node.store,
                behaviors=self.behaviors, local_steps=self.steps, dp=self.dp,
                lazy_skip_range=tuple(self.mix["lazy_skip_range"]),
                seed=cohort_seed, kernels=self.kernels)
            self.sch.add_task(FLTaskSpec(
                f"e{e}t{t}", rounds=self.rounds, reward=float(self.mix["reward"]),
                n_select=self.k, init_seed=init_seed), cohort)
        self.sch.run()
        if self.fault == "unsettled_tx":
            node._tx_batch("submitLocalModel", [node.trainer_ids[0]])
        calls = {f: node.protocol_calls.get(f, 0) - calls0.get(f, 0)
                 for f in self.ref_mod.FNS}
        rows = rollup.gas_log[g0:]
        self.log.append({
            "rep_before": rep_before, "seeds": seeds, "calls": calls,
            "seq": (seq0, rollup._next_seq), "batches": (g0, len(rollup.gas_log)),
            "commit": [int(r["commit"]) for r in rows],
            "mega": self.sch.mega_windows - mega0,
            "subs": [[len(rec.idxs) for rec in self._rounds.get(tid, [])]
                     for tid in (rt.task_id for rt in self.sch.runtimes)]})
        self._keep(e, book_before)
        self.unsettled.append((e, chain.n_submitted))
        self.epoch += 1
        return self._settle()

    def _on_round(self, rec) -> None:
        """The scheduler's round records, kept for this epoch."""
        self._rounds.setdefault(rec.task_id, []).append(rec)

    def _keep(self, e: int, book_before) -> None:
        """Hold the round records of the epochs ``check`` replays: the
        seeded ones and the latest."""
        self.kept = {k: v for k, v in self.kept.items()
                     if k in self.replay_at}
        tasks = []
        for rt in self.sch.runtimes:
            tasks.append({
                "sel": list(rt.sel_idx),
                "history": self._rounds.get(rt.task_id, []),
                "final": rt.params,
                "payouts": {self.node.trainer_index(k): v
                            for k, v in rt.result.payouts.items()}})
        self.kept[e] = {"book_before": book_before, "tasks": tasks,
                        "reputation_after": np.asarray(
                            self.node.book.reputation)}

    def _settle(self) -> int:
        """Txs of the epochs whose receipts are all finalized and whose L1
        txs are all in blocks by now."""
        done = 0
        rollup = self.node.rollup
        while self.unsettled and \
                self.unsettled[0][1] <= self.node.chain.n_confirmed:
            e = self.unsettled[0][0]
            lo, hi = self.log[e]["batches"]
            if not all(b in rollup.batch_settle_ref for b in range(lo, hi)):
                break
            self.unsettled.popleft()
            done += sum(self.log[e]["calls"].values())
        return done

    # -- after the window -----------------------------------------------------
    def window_counters(self) -> Dict[str, int]:
        """The program's ``repro.obs`` counters over the measured epochs."""
        from repro import obs
        c0 = self._obs0 or {}
        return {k: v - c0.get(k, 0) for k, v in obs.counters().items()}

    def agg_bytes(self) -> int:
        """HBM bytes the measured epochs' Eq. 1 merges and Eq. 4 distance
        passes need (``harness.fl_costs``)."""
        from harness import fl_costs
        total = 0
        for x in self.log[self.first_measured:]:
            for task in x["subs"]:
                total += sum(fl_costs.merge_bytes(k) for k in task)
                total += fl_costs.distance_bytes(task[-1])
        return total

    def window_counts(self) -> Tuple[int, int]:
        """(txs emitted in the measured epochs, those not settled by the
        end)."""
        measured = self.log[self.first_measured:]
        attempted = sum(sum(x["calls"].values()) for x in measured)
        unsettled = sum(sum(self.log[e]["calls"].values())
                        for e, _ in self.unsettled if e >= self.first_measured)
        return attempted, unsettled + self._pending_txs()

    def _pending_txs(self) -> int:
        r = self.node.rollup
        return r._next_seq - r._sealed_seq

    def _receipts_wrong(self) -> int:
        from repro.api import TxReceipt
        lo = self.log[self.first_measured]["seq"][0]
        hi = self.log[-1]["seq"][1]
        rng = rng_for(self.seed, stream=3)
        wrong = 0
        for seq in rng.integers(lo, hi, RECEIPT_SAMPLES):
            r = self.client.refresh(TxReceipt("", "", 0, 0.0, seq=int(seq)))
            if r.status != "finalized" or r.block is None:
                wrong += 1
        return wrong

    def _epoch_record(self, e: int) -> Dict:
        """An epoch's replay input, in the reference's terms (host arrays;
        the fault of ``RECORD_FAULTS`` applied)."""
        import jax
        kept = self.kept[e]
        b = kept["book_before"]
        book = self.ref_mod.Book(
            *(np.asarray(x, np.float64) for x in (
                b.reputation, b.n_tasks, b.good_history, b.age_history,
                b.interactions_with)), float(b.interactions_total))
        host = lambda t: jax.tree.map(np.asarray, t)
        tasks = []
        for t, task in enumerate(kept["tasks"]):
            rounds = []
            for r, rec in enumerate(task["history"]):
                rounds.append({
                    "params_in": host(rec.params_in), "idxs": rec.idxs,
                    "stacked": host(rec.stacked), "table": rec.table,
                    "scores": rec.scores, "params_out": host(rec.params_out)})
            tasks.append({
                "sel": task["sel"], "cohort_seed": self.log[e]["seeds"][t],
                "rounds": rounds, "final": host(task["final"]),
                "payouts": task["payouts"],
                "rows": [self.gen.batch_rows(
                    self.seed, e, t, r, self.n, self.steps, self.batch,
                    self.data.per) for r in range(self.rounds)]})
        ep = {"book_before": book, "tasks": tasks,
              "reputation_after": kept["reputation_after"]}
        if self.fault in RECORD_FAULTS:
            self._break(ep)
        return ep

    def _break(self, ep: Dict) -> None:
        """What a program with ``self.fault`` would have recorded."""
        import jax
        mal = [i for i, b in enumerate(self.behaviors) if b == "malicious"]
        good = [i for i, b in enumerate(self.behaviors) if b == "good"]
        for task in ep["tasks"]:
            for rec in task["rounds"]:
                s = np.asarray(rec["scores"], np.float64)
                if self.fault == "drop_update":
                    s = s.copy()
                    s[0] = 0.0
                elif self.fault == "uniform_merge":
                    s = np.ones_like(s)
                if self.fault in ("drop_update", "uniform_merge"):
                    rec["params_out"] = jax.tree.map(
                        lambda l: np.asarray(l, np.float32),
                        self.ref_mod.merge(rec["stacked"], s))
                elif self.fault == "malicious_as_good":
                    idxs = list(rec["idxs"])
                    m = next(i for i in mal if i in idxs)
                    g = next(i for i in good if i in idxs)
                    table = np.array(rec["table"])
                    table[:, idxs.index(m)] = table[:, idxs.index(g)]
                    rec["table"] = table

    def _logit_gap(self) -> float:
        """Largest logit difference between the program's forward pass
        (the DON's, at the configuration's precision) and the
        reference's, on the last merged model and one oracle's slice."""
        import jax
        forward = getattr(self.model, "forward", None)
        if forward is None:
            return float("nan")
        rt = self.sch.runtimes[-1]
        n = len(self.data.val_y) // int(self.cfg["data"]["n_oracles"])
        x = self.data.val_x[:n]
        got = np.asarray(jax.jit(forward)(rt.params, {"images": x}))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(self.ref_mod.logits)(
                jax.tree.map(np.asarray, rt.params), x))
        return float(np.abs(got - want).max())

    def check(self, control: bool = False) -> List[Tuple[str, int, int]]:
        """Numbers compared with the reference, each with its limit.
        ``control`` puts a program that leaves one trainer's update out of
        every merge in the program's place."""
        ref = self.ref_mod
        t0 = time.perf_counter()
        fault = self.fault
        if control:
            self.fault = "drop_update"
        wrong = collections.Counter()
        mix = self.mix
        kind = ref.behaviors(mix["behaviors"], self.n)
        for e in range(self.first_measured, self.epoch):
            x = self.log[e]
            sel = ref.selection(x["rep_before"])[:self.k]
            parts = [ref.participation(s, self.rounds, sel, kind == "lazy",
                                       mix["lazy_skip_range"])
                     for s in x["seeds"]]
            counts, commit, settle = ref.emission(
                parts, self.k, int(self.cfg["node"]["batch_size"]), self.gas)
            wrong["counts_wrong"] += sum(counts[f] != x["calls"][f]
                                         for f in ref.FNS)
            got = x["commit"]
            wrong["gas_wrong"] += abs(len(got) - len(commit)) + sum(
                a != b for a, b in zip(got, commit))
            lo, hi = x["batches"]
            rows = self.node.rollup.gas_log[lo:hi]
            paid = sum(r["verify"] + r["execute"] for r in rows)
            wrong["gas_wrong"] += int(abs(paid - settle) > 1e-6 * settle)
            wrong["megastep_wrong"] += int(x["mega"] != self.rounds)
        wrong["receipts_wrong"] += self._receipts_wrong()
        o = mix["optimizer"]
        dp = mix["dp"]
        hp = ref.Hyper(float(o["lr"]), float(o["beta1"]),
                       float(o["grad_clip"]), float(dp["clip_norm"]),
                       float(dp["noise_multiplier"]) * float(dp["clip_norm"])
                       / math.sqrt(self.batch))
        worst = collections.Counter()
        for e in sorted(set(self.replay_at) & set(self.kept)
                        | {self.epoch - 1}):
            w, big = ref.replay_epoch(
                self._epoch_record(e), self.data, hp, mix["behaviors"],
                mix["lazy_skip_range"], int(self.cfg["data"]["n_oracles"]),
                float(mix["reward"]))
            wrong.update(w)
            for k, v in big.items():
                worst[k] = max(worst[k], v) if isinstance(v, float) \
                    else worst[k] + v
        print(f"[bench] fl replay largest errors {dict(worst)}, program "
              f"logits {self._logit_gap():.3g} from the reference's "
              f"(tolerances {ref.TOL})", flush=True)
        wrong["fig3_wrong"] += ref.fig3_wrong(
            np.asarray(self.node.book.reputation), mix["behaviors"])
        self.fault = fault
        print(f"[bench] fl check {time.perf_counter() - t0:.1f} s, "
              f"epochs {self.first_measured}..{self.epoch - 1}, replayed "
              f"{sorted(set(self.replay_at) & set(self.kept))} and "
              f"{self.epoch - 1}", flush=True)
        return ref.compare(wrong)


def _take(x, y, rows):
    """A round's batches: leaves (trainers, steps, batch, ...)."""
    return {"images": x[rows], "labels": y[rows]}


def _pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())
