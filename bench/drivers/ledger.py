"""Ledger driver: Table-I txs through the node's fused window loop.

Entry point family: ``repro.api.NodeClient`` over the configuration's
``NodeSpec`` (one ``VectorRollup``, or a ``ShardedRollup`` fabric), fed
one ``core.fused.FusedWindowLoop`` plan per modeled window: submit the
window's txs, seal and settle them (``flush``), pump the prover and pack
the window's L1 block, then ``execute()``.  A unit of work is one
window; its txs count once the window is settled on the L1: every
commit of its batches and its verify and execute txs are in an L1 block.

Set-up opens every account of the configuration (the first state root
folds the whole committed word buffer), generates the traffic pool,
compiles every kernel shape the pool's windows can reach, and runs the
mix's warm-up windows through the same loop.  The node's event log is a
ring of ``event_cap`` events, as a long-running node keeps it; the
driver reads each window's events as a client would, by cursor.
"""
from __future__ import annotations

import collections
import json
import math
from typing import Dict, List, Tuple

import numpy as np

from harness import traffic as traffic_gen
from harness.traffic import rng_for

#: roots are compared at this many seeded windows, plus the last one
ROOT_SAMPLES = 6
#: receipts read back at this many seeded txs
RECEIPT_SAMPLES = 256


class Driver:
    RATE_METRIC = "ledger_tx_per_s"
    TAIL_METRIC = "ledger_window_p95_ms"

    def __init__(self, cfg: Dict, mix: Dict, seed: int, seconds: float,
                 devices, registry):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.seconds = seconds
        self.ref_mod = registry.module("reference", cfg["reference"])
        self.gas = json.loads(
            (registry.dir / "reference" / "table1_gas.json").read_text())
        self.node = cfg["node"]
        self.k = int(self.node["shards"])
        self.n_accounts = int(cfg["accounts"])
        self.registry = registry
        self.next = 0
        self.warmup = int(mix["warmup_windows"])
        self.prov: List = []               # per window: seq start or seqs
        self.unsettled = collections.deque()   # (window, L1 end index)
        self.digests: List[List[int]] = [[] for _ in range(self.k)]
        self.roots: Dict[int, tuple] = {}

    # -- set-up ---------------------------------------------------------------
    def _spec(self):
        from repro.api import ChainSpec, NodeSpec, RollupSpec, ShardSpec
        n = self.node
        shards = (ShardSpec(count=self.k, route=n["route"], mesh=n["mesh"])
                  if self.k > 1 else None)
        return NodeSpec(
            chain=ChainSpec(n_validators=int(n["n_validators"]),
                            block_time=float(n["block_time_s"]),
                            block_gas_limit=int(n["block_gas_limit"])),
            rollup=RollupSpec(batch_size=int(n["batch_size"]),
                              n_lanes=int(n["n_lanes"])),
            shards=shards)

    def setup(self) -> None:
        from repro.api import NodeClient
        from repro.core.engine import FnRegistry
        n_pool = self.warmup + math.ceil(
            self.seconds * float(self.mix["max_windows_per_s"])) + 1
        self.windows = traffic_gen.generate(
            self.mix, self.seed, self.registry, n_windows=n_pool,
            n_accounts=self.n_accounts, l1_gas=self.gas["l1_per_call"])
        self.fns = FnRegistry(self.windows.fns)
        self.client = NodeClient.from_spec(self._spec())
        self.target = self.client.target
        self.chain = self.client.chain
        self.log = self.chain.events
        self.log.cap = int(self.node["event_cap"])
        self.cursor = self.log.next_cursor
        self.state = self.client._state_arrays()
        self.state.ensure(self.n_accounts)
        self.client.state_root()
        if self.k > 1:
            self.target.fabric_root()
        self._warm_kernels(n_pool)
        for _ in range(self.warmup):
            self.step()
        self.first_measured = self.next

    def _lane_sizes(self, w: int) -> List[int]:
        _, _, _, sender = self.windows.window(w)
        if self.k == 1:
            return [len(sender)]
        own = self.ref_mod.owner(sender, self.k)
        return np.bincount(own, minlength=self.k).tolist()

    def _warm_kernels(self, n_pool: int) -> None:
        """Compile every shape the pool's windows can reach: the seal
        fold at each per-lane window size, and the L1 block packer at
        every power-of-two mempool bucket up to the pool's last window."""
        from repro.kernels.factory import get_kernel
        bs = int(self.node["batch_size"])
        sizes = sorted({n for w in range(n_pool)
                        for n in self._lane_sizes(w) if n})
        if self.k == 1:
            seal = get_kernel("batch_seal")
            for n in sizes:
                words = np.zeros(4 * n, np.uint32)
                seal(words, np.arange(0, n, bs, dtype=np.int64) * 4)
                seal(words, np.zeros(1, np.int64))
        # the L1 gets one commit per batch plus verify+execute per shard
        l1_max = sum(-(-n // bs) + 2 for w in range(n_pool)
                     for n in self._lane_sizes(w) if n)
        pack = get_kernel("block_pack")
        size = 16
        while size // 2 < max(l1_max, 16):
            tmax = np.arange(size, dtype=np.float64)
            gcum = np.arange(1, size + 1, dtype=np.int64)
            pack(tmax, gcum, np.ones(1), np.array([size], np.int64),
                 int(self.node["block_gas_limit"]), 0)
            size *= 2

    # -- the window loop ------------------------------------------------------
    def step(self) -> int:
        import jax
        from repro.core.engine import TxArrays
        from repro.core.fused import FusedWindowLoop
        w = self.next
        if w >= len(self.windows):
            raise RuntimeError("traffic pool exhausted: raise the mix's "
                               "max_windows_per_s")
        t, gas, fn, sender = self.windows.window(w)
        t_end = (w + 1) * self.windows.window_s
        with jax.profiler.TraceAnnotation("ledger.record"):
            loop = FusedWindowLoop(self.chain, self.target)
            prov = loop.submit(self.target,
                               TxArrays(t, gas, fn, sender, self.fns))
            loop.flush()
            loop.pump(t_end)
            loop.run_until(t_end)
        with jax.profiler.TraceAnnotation("ledger.execute"):
            loop.execute()
        with jax.profiler.TraceAnnotation("ledger.read"):
            self.prov.append(prov[0] if self.k == 1 else prov[1])
            self._read_events()
            self.unsettled.append((w, self.chain.n_submitted))
            self.next += 1
            return self._settle()

    def _read_events(self) -> None:
        """This window's seal digests and settled roots, by cursor."""
        from repro.core.events import BatchSealed, WindowSettled
        for e in self.log.since(self.cursor):
            if isinstance(e, BatchSealed):
                self.digests[e.shard or 0].append(int(e.digest))
            elif isinstance(e, WindowSettled) and e.shard is None:
                self.roots[e.window] = (
                    (e.state_root,) if self.k == 1 else
                    (e.state_root, e.fabric_root) + tuple(e.shard_roots))
        self.cursor = self.log.next_cursor

    def _settle(self) -> int:
        """Txs of the windows whose L1 txs are all in blocks by now."""
        done = 0
        confirmed = self.chain.n_confirmed
        while self.unsettled and self.unsettled[0][1] <= confirmed:
            w, _ = self.unsettled.popleft()
            done += int(self.windows.offsets[w + 1] - self.windows.offsets[w])
        return done

    # -- after the window -----------------------------------------------------
    def lanes(self):
        return list(self.target.shards) if self.k > 1 else [self.target]

    def window_counts(self) -> Tuple[int, int]:
        """(txs attempted in the measured windows, those without a
        finalized receipt or not settled in an L1 block by the end)."""
        lo = int(self.windows.offsets[self.first_measured])
        hi = int(self.windows.offsets[self.next])
        unfinalized = sum(int(r["n_txs"]) for lane in self.lanes()
                          for r in lane.gas_log if "aggregate" not in r)
        unsettled = sum(int(self.windows.offsets[w + 1]
                            - self.windows.offsets[w])
                        for w, _ in self.unsettled)
        return hi - lo, unfinalized + unsettled

    def outputs(self) -> Dict:
        """What the timed path committed, in the reference's terms."""
        batches = [list(zip((int(r["n_txs"]) for r in lane.gas_log),
                            (int(r["commit"]) for r in lane.gas_log),
                            (int(d) for d in lane.batch_digests)))
                   for lane in self.lanes()]
        blocks = [(b.n_txs, b.gas_used) for b in self.chain.blocks[1:]]
        return {"batches": batches, "window_digests": self.digests,
                "roots": self.roots, "blocks": blocks}

    def receipt_batches(self, picks: List[Tuple[int, int]]) -> List:
        """(shard, global batch id, status) of sampled txs, read back
        through ``NodeClient.refresh``."""
        from repro.api import TxReceipt
        out = []
        for w, i in picks:
            seq = int(self.prov[w] + i) if self.k == 1 else \
                int(self.prov[w][i])
            _, _, _, sender = self.windows.window(w)
            shard = int(self.ref_mod.owner(sender[i:i + 1], self.k)[0]) \
                if self.k > 1 else 0
            r = self.client.refresh(TxReceipt("", "", 0, 0.0, seq=seq,
                                              shard=shard))
            out.append((shard, r.batch, r.status))
        return out

    def reference(self, sample: List[int], drop_last: bool = False):
        """The plain reference over every window this run drove.
        ``drop_last`` is the control: it loses each window's last tx."""
        ref = self.ref_mod.Ledger(self.gas, self.node, self.n_accounts,
                                  sample)
        order = self.ref_mod.FN_ORDER
        remap = np.array([order.index(f) for f in self.windows.fns])
        for w in range(self.next):
            t, gas, fn, sender = self.windows.window(w)
            if drop_last:
                t, gas, fn, sender = t[:-1], gas[:-1], fn[:-1], sender[:-1]
            ref.window(t, gas, remap[fn], sender)
        return ref

    def kernel_bytes(self) -> int:
        """HBM bytes the ledger kernels need over the measured windows
        (``harness.costs``): per lane the two seal folds, the refold of
        every state chunk the window dirtied (the flat root, and each
        shard's root on a fabric), and one L1 block's packing."""
        from harness import costs
        ref = self.ref_mod
        bs = int(self.node["batch_size"])
        if self.k > 1:
            own = ref.owner(np.arange(self.n_accounts), self.k)
            shard_rows = [np.flatnonzero(own == k) for k in range(self.k)]
        total, mempool = 0, 0
        for w in range(self.next):
            _, _, _, sender = self.windows.window(w)
            sizes = self._lane_sizes(w)
            mempool += sum(-(-n // bs) + 2 for n in sizes if n)
            if w < self.first_measured:
                continue
            for n in sizes:
                if n:
                    total += costs.seal_bytes(n, -(-n // bs))
                    total += costs.seal_bytes(n, 1)
            chunks = ref.dirty_chunks(sender, self.n_accounts)
            if self.k > 1:
                s_own = ref.owner(sender, self.k)
                for k in range(self.k):
                    pos = np.searchsorted(shard_rows[k], sender[s_own == k])
                    chunks += ref.dirty_chunks(pos, shard_rows[k].size)
            total += costs.dirty_fold_bytes(chunks)
            total += costs.block_pack_bytes(1, mempool)
        return total

    def samples(self):
        rng = rng_for(self.seed, stream=1)
        n = self.next
        wins = sorted(set(rng.choice(n, min(ROOT_SAMPLES, n),
                                     replace=False).tolist()) | {n - 1})
        picks = []
        for _ in range(RECEIPT_SAMPLES):
            w = int(rng.integers(0, n))
            size = int(self.windows.offsets[w + 1] - self.windows.offsets[w])
            picks.append((w, int(rng.integers(0, size))))
        return wins, picks

    def check(self, control: bool = False) -> List[Tuple[str, int, int]]:
        """Numbers compared with the reference, each with its limit.
        ``control`` puts the control reference in the program's place."""
        wins, picks = self.samples()
        ref = self.reference(wins)
        if control:
            ctl = self.reference(wins, drop_last=True)
            got = {"batches": ctl.batches,
                   "window_digests": ctl.window_digests,
                   "roots": ctl.roots, "blocks": ctl.blocks}
            receipts = [(0, int(ctl.tx_batch[w][i])
                         if i < len(ctl.tx_batch[w]) else None, "finalized")
                        for w, i in picks]
        else:
            got = self.outputs()
            receipts = self.receipt_batches(picks)
        return self.ref_mod.compare(got, ref, wins, picks, receipts)
