"""Fused compiled window loop over the SoA ledger hot path.

The Python-stepped scheduler drives every window through four separate
round-trips — pump the prover, seal lane batches, settle, and pack L1
blocks (``fl/scheduler.Scheduler.run``).  At small per-window tx counts
the vector engine's per-call Python overhead (not the array math)
dominates, so per-task throughput collapses as task count grows.

``FusedWindowLoop`` is a plan-then-execute driver for the same loop:

  * during the window loop, ledger calls append cheap **plan entries**
    (chain staging, seal/pump/settle points, block-production edges)
    instead of executing eagerly;
  * ``execute()`` then replays the plan once:

      1. every seal point's lane/batch structure, commit gas, timestamps
         and digests are computed in ONE vectorized pass over all
         windows (the per-batch xor-roots and per-window update digests
         both route through the ``batch_seal`` kernel — one call each
         for the whole run);
      2. the plan is walked in order, applying the precomputed seal
         slices, pumping the prover and staging L1 traffic exactly as
         the stepped path would — so event order, arrival indices, gas
         rows and state-handler application order are bit-identical;
      3. every deferred ``run_until`` edge becomes rows of one block
         grid, packed by a single ``block_pack`` kernel call (a jitted
         ``lax.scan`` over blocks with donated SoA buffers — N windows
         of blocks as one XLA program instead of N Python round-trips),
         and the resulting ``BlockPacked`` events are spliced back into
         the typed stream at the positions the stepped path would have
         emitted them.

Equivalence contract (pinned by tests/test_fused.py): a fused run and a
stepped run of the same schedule produce identical typed event streams,
state roots, gas logs, blocks, confirm times and results.  The only
visible difference is legacy ``EventHooks`` callback TIMING: string-key
subscribers see ``block_packed`` callbacks at ``execute()`` instead of
mid-run (relative order among block_packed callbacks is preserved).

Scope: ``VectorChain`` alone, ``VectorChain`` + ``VectorRollup``, or
``VectorChain`` + ``ShardedRollup`` — the fabric runs as K shard
**lanes**: routing decisions (hash split / least-loaded argmin / task
pins) are taken once at record time against the live ``_submitted``
counters, each lane's seal groups run through the same one-concat/
lexsort precompute, the K lanes' digest folds batch into the
``shard_seal`` kernel (kernels/shard_lanes.py — optionally
``shard_map``-ped over a ``"shard"`` device mesh), and every window
closes through ``ShardedRollup._finish_window`` exactly like a stepped
seal.  The object engines keep the stepped path
(``Scheduler(fused="auto")`` falls back automatically, with a one-time
log).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.core.engine import (BlockStats, TxArrays, VectorChain,
                               VectorRollup)
from repro.core.events import BatchSealed, BlockPacked


def supports_fused(chain, rollup) -> bool:
    """True when the (chain, rollup) pair can run the fused loop: a SoA
    L1 and (optionally) a SoA rollup face.  Backends declare themselves
    via a ``fused_capable`` class marker (VectorChain, VectorRollup and
    ShardedRollup set it True; the object engines lack it and fall back
    to the stepped path)."""
    if not getattr(chain, "fused_capable", False):
        return False
    return rollup is None or getattr(rollup, "fused_capable", False)


@dataclasses.dataclass
class _SealPrep:
    """One seal point, fully precomputed (None group -> empty seal).

    Everything the stepped ``seal()`` derives per call — batch structure,
    commit gas, timestamps, digests, gas rows, even the commit TxArrays —
    is built in the one bulk pass; applying a seal is pure bookkeeping."""

    txs: TxArrays                # the group's txs, arrival order
    n_txs: np.ndarray            # per-batch tx counts
    now: np.ndarray              # per-batch max submit_time
    roots: np.ndarray            # per-batch tx xor-roots (u32)
    update_digest: int           # whole-group merged-buffer digest
    arrival_batch: np.ndarray    # per-tx GLOBAL batch id (arrival order)
    first: int                   # global id of the group's first batch
    rows: List[Dict[str, Any]]   # prebuilt gas_log rows
    commit_batch: TxArrays       # time-sorted L1 commit txs
    inv_post: np.ndarray         # batch j -> its commit's index in post


class FusedWindowLoop:
    """Plan-then-execute driver for one stepped scheduler run.

    Record phase (the window loop): ``submit`` / ``seal`` / ``pump`` /
    ``run_until`` / ``flush``.  Rollup-bound txs stage into the real
    pending queue immediately (their order only matters relative to seal
    points, which the plan tracks by watermark); chain-bound txs are
    journaled so their arrival indices interleave correctly with the
    seal commits and settlement txs replayed later.  ``execute()`` runs
    the whole plan; afterwards the ledger state is indistinguishable
    from a stepped run.
    """

    def __init__(self, chain: VectorChain,
                 rollup: Optional[VectorRollup] = None):
        assert supports_fused(chain, rollup), \
            "fused loop needs a VectorChain (+ optional SoA rollup face)"
        self.chain = chain
        self.rollup = rollup
        # the sharded fabric runs as K shard LANES; a plain VectorRollup
        # is the one-lane case of the same machinery
        self.fabric = rollup if hasattr(rollup, "shards") else None
        self._lanes: List[VectorRollup] = (
            list(rollup.shards) if self.fabric is not None
            else ([rollup] if rollup is not None else []))
        self._plan: List[Tuple] = []
        # journaled per-lane rollup staging; adopt anything already
        # pending so the first planned seal covers it, like a stepped
        # seal would
        self._r_batches: List[List[TxArrays]] = [[] for _ in self._lanes]
        for k, lane in enumerate(self._lanes):
            if lane._pending:
                self._r_batches[k].extend(lane._pending)
                lane._pending, lane._pending_n = [], 0
        self._executed = False

    # -- record phase ----------------------------------------------------------
    def _stage(self, k: int, batch: TxArrays) -> Tuple[int, int]:
        """Journal one batch into lane ``k``, assigning its seq range now
        (receipts hold [lo, hi) before execute, same as a live submit)."""
        lane = self._lanes[k]
        lo = lane._next_seq
        lane._next_seq += len(batch)
        self._r_batches[k].append(batch)
        return lo, lo + len(batch)

    def submit(self, target, batch: TxArrays, shard=None):
        """Route one SoA batch: journaled, not staged — rollup txs only
        order relative to seal points (watermarked), chain txs replay
        in-order so arrival indices interleave with commits exactly.

        On the fabric the routing decision itself happens NOW (vectorized
        hash split / least-loaded argmin over the live ``_submitted``
        counters / a task-pinned ``shard``), exactly as the stepped
        ``ShardedRollup.submit_arrays`` would take it, and the per-tx
        ``(shard, seq)`` provenance is returned immediately."""
        if target is self.rollup and self.rollup is not None:
            rollup = self.rollup
            if batch.fns is not rollup.fns:
                remap = np.array([rollup.fns.id(n)
                                  for n in batch.fns.names], np.int32)
                batch = TxArrays(batch.submit_time, batch.gas,
                                 remap[batch.fn_id] if len(batch) else
                                 batch.fn_id, batch.sender_id, rollup.fns)
            if self.fabric is None:
                return self._stage(0, batch)
            return self._route_fabric(batch, shard)
        assert target is self.chain, "unknown fused submit target"
        if batch.fns is not self.chain.fns:
            # same remap submit_arrays would do — at RECORD time, so fn
            # names register in the stepped path's order
            remap = np.array([self.chain.fns.id(n)
                              for n in batch.fns.names], np.int32)
            batch = TxArrays(batch.submit_time, batch.gas,
                             remap[batch.fn_id] if len(batch) else
                             batch.fn_id, batch.sender_id, self.chain.fns)
        self._plan.append(("tx", batch))
        return None

    def _route_fabric(self, batch: TxArrays, shard):
        """The stepped ``ShardedRollup.submit_arrays`` routing, replayed
        at record time: same ``_submitted`` bookkeeping, same wire-cost
        accounting, same ``(shard_of, seq_of)`` provenance — the only
        difference is that the sub-batches journal into lanes instead of
        landing in shard pending queues."""
        fab = self.fabric
        n = len(batch)
        if shard is None and fab.route == "least_loaded":
            shard = int(np.argmin(fab._submitted))
        if shard is not None or fab.n_shards == 1:
            k = int(shard or 0)
            fab._submitted[k] += n
            pinned = np.zeros(fab.n_shards, np.int64)
            pinned[k] = n
            fab._wire_submit(pinned)
            lo, hi = self._stage(k, batch)
            return (np.full(n, k, np.int64),
                    np.arange(lo, hi, dtype=np.int64))
        from repro.core.shards import _hash_route
        lanes = _hash_route(batch.sender_id, fab.n_shards)
        fab._wire_submit(np.bincount(lanes, minlength=fab.n_shards))
        seq_of = np.empty(n, np.int64)
        for k in range(fab.n_shards):
            m = lanes == k
            if m.any():
                fab._submitted[k] += int(m.sum())
                lo, hi = self._stage(k, TxArrays(
                    batch.submit_time[m], batch.gas[m], batch.fn_id[m],
                    batch.sender_id[m], fab.fns))
                seq_of[m] = np.arange(lo, hi, dtype=np.int64)
        return lanes.astype(np.int64), seq_of

    def covers(self, target) -> bool:
        return target is self.chain or (self.rollup is not None
                                        and target is self.rollup)

    def seal(self):
        """Plan a seal point at the current per-lane staging watermarks."""
        assert self.rollup is not None
        # the stepped path registers the commit fn at its first seal —
        # keep the registry's id order identical
        self.rollup.fns.id("rollup_commit")
        self._plan.append(("seal",
                           tuple(len(rb) for rb in self._r_batches)))

    def pump(self, t_end: float):
        self._plan.append(("pump", float(t_end)))

    def run_until(self, t_end: float):
        self._plan.append(("blocks", float(t_end)))

    def flush(self):
        """Plan the stepped ``rollup.flush()``: tail seal + session close
        + forced drain."""
        self.seal()
        self._plan.append(("settle",))

    def sync_state(self, state, ids: np.ndarray, reputation: np.ndarray,
                   balances, stake):
        """Plan a cross-window state scatter (the node's fabric-state
        sync) so it lands between the seal points exactly where the
        stepped path wrote it — per-window state roots depend on it."""
        self._plan.append(("sync", state, np.asarray(ids, np.int64),
                           np.asarray(reputation, np.float32),
                           np.asarray(balances, np.float64),
                           np.asarray(stake, np.float64)))

    # -- execute: one pass over the plan ---------------------------------------
    def execute(self) -> None:
        assert not self._executed, "fused plan already executed"
        self._executed = True
        obs.count("windows")
        chain, rollup = self.chain, self.rollup
        with obs.span("ledger.seal"):
            preps = self._prepare_seals()
        chain_buf: List[TxArrays] = []

        def flush_chain():
            if not chain_buf:
                return
            with obs.span("ledger.pool"):
                if len(chain_buf) == 1:
                    chain.submit_arrays(chain_buf[0])
                else:
                    chain.submit_arrays(TxArrays(
                        np.concatenate([b.submit_time for b in chain_buf]),
                        np.concatenate([b.gas for b in chain_buf]),
                        np.concatenate([b.fn_id for b in chain_buf]),
                        np.concatenate([b.sender_id for b in chain_buf]),
                        chain.fns))
            chain_buf.clear()

        times: List[float] = []
        n_vis: List[int] = []
        # (event position, first deferred block, #blocks) per blocks edge
        markers: List[Tuple[int, int, int]] = []
        cursor = chain.blocks[-1].time
        seal_i = 0
        for entry in self._plan:
            op = entry[0]
            if op == "tx":
                chain_buf.append(entry[1])
            elif op == "seal":
                flush_chain()
                with obs.span("ledger.seal"):
                    if self.fabric is not None:
                        # lanes seal in shard order, then the fabric
                        # merges the window — the stepped
                        # ShardedRollup.seal()
                        self.fabric._finish_window(
                            [self._apply_seal(preps[k][seal_i], lane)
                             for k, lane in enumerate(self._lanes)])
                    else:
                        self._apply_seal(preps[0][seal_i], rollup)
                seal_i += 1
            elif op == "pump":
                flush_chain()
                with obs.span("ledger.prove"):
                    rollup.pump(entry[1])
            elif op == "settle":
                flush_chain()
                with obs.span("ledger.prove"):
                    rollup.settle_session()
                    if self.fabric is not None:
                        rollup.prover.drain()  # fabric-wide forced drain
                    else:
                        rollup.prover.drain(rollup)
            elif op == "sync":
                _, state, ids, rep, bal, stake = entry
                state.ensure_ids(ids)
                state.reputation[ids] = rep
                state.balances[ids] = bal
                state.stake[ids] = stake
                state.mark_dirty(ids)
            elif op == "blocks":
                flush_chain()
                t_end = entry[1]
                lo = len(times)
                while cursor < t_end:
                    cursor += chain.block_time
                    times.append(cursor)
                    n_vis.append(chain.n_submitted)
                if len(times) > lo:
                    markers.append((chain.events.next_cursor, lo,
                                    len(times) - lo))
            else:                                       # pragma: no cover
                raise AssertionError(f"unknown plan op {op!r}")
        flush_chain()
        self._pack_blocks(np.asarray(times, np.float64),
                          np.asarray(n_vis, np.int64), markers)

    # -- seal precompute + per-point application -------------------------------
    def _collect_groups(self, k: int) -> List[List[TxArrays]]:
        """Split lane ``k``'s journaled staging at the planned watermarks;
        batches past the last watermark return to the lane's real pending
        queue (they are what a stepped run would leave unsealed)."""
        groups, prev = [], 0
        for entry in self._plan:
            if entry[0] == "seal":
                groups.append(self._r_batches[k][prev:entry[1][k]])
                prev = entry[1][k]
        tail = self._r_batches[k][prev:]
        if tail:
            lane = self._lanes[k]
            lane._pending.extend(tail)
            lane._pending_n += sum(len(b) for b in tail)
        return groups

    def _prepare_seals(self) -> List[List[Optional[_SealPrep]]]:
        """One vectorized pass per lane computing every seal point's
        batch structure, commit gas, timestamps, gas rows and commit txs
        (the stepped ``VectorRollup.seal`` math, all windows at once —
        applying a seal afterwards is pure bookkeeping), followed by ONE
        batched digest fold across all lanes: on the fabric the K lanes'
        segmented xor-folds stack into the ``shard_seal`` kernel's
        ``(K, W)`` word grid (two calls for the whole run — per-batch tx
        roots and per-window update digests), optionally ``shard_map``-ped
        over the ``"shard"`` device mesh.  Indexed ``[lane][seal_i]``."""
        if self.rollup is None:
            return []
        structs = [self._lane_struct(lane, self._collect_groups(k))
                   for k, lane in enumerate(self._lanes)]
        self._fold_digests(structs)
        return [self._lane_preps(lane, structs[k])
                for k, lane in enumerate(self._lanes)]

    def _lane_struct(self, rollup: VectorRollup,
                     groups: List[List[TxArrays]]) -> Optional[Dict]:
        """Everything the stepped ``seal()`` derives for one lane's
        groups EXCEPT the digest folds (those batch across lanes)."""
        sizes = [sum(len(b) for b in g) for g in groups]
        live = [i for i, s in enumerate(sizes) if s > 0]
        if not live:
            return None
        cat = [b for i in live for b in groups[i]]
        t = np.concatenate([b.submit_time for b in cat])
        g = np.concatenate([b.gas for b in cat])
        f = np.concatenate([b.fn_id for b in cat])
        s = np.concatenate([b.sender_id for b in cat])
        n = t.shape[0]
        gsz = np.array([sizes[i] for i in live], np.int64)
        gstart = np.concatenate([[0], np.cumsum(gsz)[:-1]])
        gidx = np.repeat(np.arange(len(live)), gsz)
        within = np.arange(n) - gstart[gidx]
        lane = within % rollup.n_lanes
        pos = within // rollup.n_lanes
        bil = pos // rollup.batch_size
        # group-major lane-major order: identical within-group order to
        # the stepped seal's lexsort((pos, lane))
        order = np.lexsort((pos, lane, gidx))
        lane_o, bil_o, g_o = lane[order], bil[order], gidx[order]
        seg_new = np.empty(n, bool)
        seg_new[0] = True
        seg_new[1:] = ((g_o[1:] != g_o[:-1]) | (lane_o[1:] != lane_o[:-1])
                       | (bil_o[1:] != bil_o[:-1]))
        batch_id = np.cumsum(seg_new) - 1           # global across groups
        nb = int(batch_id[-1]) + 1
        starts = np.flatnonzero(seg_new)
        fn_o, t_o = f[order], t[order]
        counts = np.zeros((nb, len(rollup.fns)), np.int64)
        np.add.at(counts, (batch_id, fn_o), 1)
        base, percall = rollup._commit_gas_vectors()
        commit = (counts > 0) @ base + counts @ percall
        n_txs = counts.sum(axis=1)
        now = np.maximum.reduceat(t_o, starts)
        words = TxArrays(t_o, g[order], fn_o, s[order],
                         rollup.fns).word_buffer()
        # global batch ids: groups seal in plan order, so ids continue
        # from the lane's current count exactly like consecutive seals
        first0 = rollup.n_batches
        arrival_batch = np.empty(n, np.int64)
        arrival_batch[order] = first0 + batch_id
        batch_group = g_o[starts]                   # group of each batch
        # per-batch commit ordering, grouped: the stepped seal posts each
        # group's commits time-sorted (stable)
        post = np.lexsort((np.arange(nb), now, batch_group))
        inv_post = np.empty(nb, np.int64)
        inv_post[post] = np.arange(nb)
        return {"live": live, "t": t, "g": g, "f": f, "s": s,
                "gsz": gsz, "gstart": gstart, "nb": nb, "starts": starts,
                "n_txs": n_txs, "now": now, "commit": commit,
                "words": words, "first0": first0,
                "arrival_batch": arrival_batch,
                "batch_group": batch_group, "post": post,
                "inv_post": inv_post, "lane_b": lane_o[starts],
                "roots": None, "gdigest": None}

    def _fold_digests(self, structs: List[Optional[Dict]]) -> None:
        """Fill every lane's per-batch tx roots and per-group update
        digests.  Single lane: the two ``batch_seal`` segmented folds of
        the stepped path.  Fabric: the K lanes' folds stack into the
        ``shard_seal`` kernel — two calls total, each folding every
        lane's segments at once over the lane-rows word grid."""
        live = [st for st in structs if st is not None]
        if not live:
            return
        if self.fabric is None:
            from repro.core.engine import xor_fold_digest_segments
            st = live[0]
            st["roots"] = xor_fold_digest_segments(
                st["words"], st["starts"] * 4)
            # per-GROUP merged-buffer digests: groups are word-contiguous
            # in lane-major order, so one more segmented fold covers all
            # the stepped path's per-seal update digests
            st["gdigest"] = xor_fold_digest_segments(
                st["words"], st["gstart"] * 4)
            return
        from repro.kernels.factory import get_kernel
        fn = get_kernel("shard_seal", self._shard_seal_impl())
        k_live = len(live)
        n_words = np.array([st["words"].shape[0] for st in live], np.int64)
        words2d = np.zeros((k_live, int(n_words.max())), np.uint32)
        for i, st in enumerate(live):
            words2d[i, : n_words[i]] = st["words"]

        def fold(key, scale):
            segs = [np.asarray(st[key], np.int64) * scale for st in live]
            n_seg = np.array([len(sg) for sg in segs], np.int64)
            starts2d = np.repeat(n_words[:, None], int(n_seg.max()), 1)
            for i, sg in enumerate(segs):
                starts2d[i, : n_seg[i]] = sg
            out = fn(words2d, starts2d, n_seg, n_words)
            return [out[i, : n_seg[i]] for i in range(k_live)]

        roots = fold("starts", 4)
        gdigs = fold("gstart", 4)
        for i, st in enumerate(live):
            st["roots"] = roots[i]
            st["gdigest"] = gdigs[i]

    def _shard_seal_impl(self) -> str:
        """Map the fabric's mesh knob to a ``shard_seal`` impl: ``"on"``
        forces the mesh-mapped kernel, ``"off"`` the NumPy mirror, and
        ``"auto"`` takes the mesh exactly when more than one local device
        exists (the NumPy mirror otherwise — at CPU lane counts the fold
        is memory-bound and the mirror wins without a real mesh)."""
        mode = getattr(self.fabric, "mesh_mode", "off")
        if mode == "on":
            return "shard_map"
        if mode == "off":
            return "numpy"
        from repro.launch.mesh import n_local_devices
        return "shard_map" if n_local_devices() > 1 else "numpy"

    def _lane_preps(self, rollup: VectorRollup,
                    st: Optional[Dict]) -> List[Optional[_SealPrep]]:
        """Assemble one lane's per-seal-point ``_SealPrep`` list from its
        structure + filled digests."""
        n_groups = sum(1 for e in self._plan if e[0] == "seal")
        preps: List[Optional[_SealPrep]] = [None] * n_groups
        if st is None:
            return preps
        live, gstart, gsz = st["live"], st["gstart"], st["gsz"]
        n_txs, now, commit = st["n_txs"], st["now"], st["commit"]
        nb, first0 = st["nb"], st["first0"]
        now_p = st["now"][st["post"]]
        commit_p = st["commit"][st["post"]]
        commit_fn = rollup.fns.id("rollup_commit")
        lane_b = st["lane_b"]
        bstart = np.searchsorted(st["batch_group"], np.arange(len(live)))
        bstop = np.concatenate([bstart[1:], [nb]])
        t, g, f, s = st["t"], st["g"], st["f"], st["s"]
        for k, i in enumerate(live):
            b0, b1 = int(bstart[k]), int(bstop[k])
            # group k is contiguous both in arrival order (concat) and in
            # the group-major sorted order, at the same slice
            tsel = slice(int(gstart[k]), int(gstart[k] + gsz[k]))
            rows = [{"batch": first0 + j, "lane": int(lane_b[j]),
                     "n_txs": int(n_txs[j]), "commit": int(commit[j]),
                     "verify": 0, "execute": 0, "total": int(commit[j])}
                    for j in range(b0, b1)]
            nb_g = b1 - b0
            commit_batch = TxArrays(
                now_p[b0:b1].astype(np.float64),
                commit_p[b0:b1].astype(np.int64),
                np.full(nb_g, commit_fn, np.int32),
                np.zeros(nb_g, np.int32), rollup.fns)
            preps[i] = _SealPrep(
                TxArrays(t[tsel], g[tsel], f[tsel], s[tsel], rollup.fns),
                n_txs[b0:b1], now[b0:b1], st["roots"][b0:b1],
                int(st["gdigest"][k]), st["arrival_batch"][tsel],
                first0 + b0, rows, commit_batch,
                st["inv_post"][b0:b1] - b0)
        return preps

    def _apply_seal(self, prep: Optional[_SealPrep],
                    rollup: VectorRollup) -> int:
        """Apply one precomputed seal point to one lane — the stepped
        ``seal()``'s bookkeeping, with all the array math already done in
        bulk.  Returns the number of batches sealed (the stepped return)."""
        if prep is None:                       # empty seal: window event
            rollup._emit_window(0)
            return 0
        n = len(prep.txs)
        if rollup._state_handlers:
            rollup._apply_state(prep.txs)
        first, nb = prep.first, len(prep.n_txs)
        rollup.batch_digests.extend(int(r) for r in prep.roots)
        rollup.update_digest = prep.update_digest
        rollup._prov_starts.append(rollup._sealed_seq)
        rollup._prov_batches.append(prep.arrival_batch)
        rollup._sealed_seq += n
        refs = rollup._l1_submit(prep.commit_batch)
        rollup.batch_commit_ref.update(
            (first + j, refs[int(prep.inv_post[j])]) for j in range(nb))
        rollup.gas_log.extend(prep.rows)
        rollup.n_batches += nb
        rollup._last_time = float(prep.now.max())
        rollup.prover.enqueue(rollup, first, prep.roots, prep.n_txs,
                              prep.now, prep.rows)
        rollup.events.emit(BatchSealed, time=rollup._last_time,
                           shard=rollup._event_shard, first_batch=first,
                           n_batches=nb, n_txs=n,
                           digest=rollup.update_digest)
        rollup._emit("batch_sealed", {
            "first_batch": first, "n_batches": nb, "n_txs": n,
            "digest": rollup.update_digest})
        rollup._emit_window(nb)
        return nb

    # -- deferred block production ---------------------------------------------
    def _pack_blocks(self, times: np.ndarray, n_vis: np.ndarray,
                     markers: List[Tuple[int, int, int]]) -> None:
        """Pack every deferred block in one ``block_pack`` kernel call
        and splice the BlockPacked events to their stepped positions."""
        if times.shape[0] == 0:
            return
        with obs.span("ledger.pool"):
            self.chain._consolidate()
        obs.count("pack.rows", self.chain._n - self.chain._ptr)
        with obs.span("ledger.pack"):
            ntx, gas_used, height0 = self._pack(times, n_vis)
        with obs.span("ledger.events"):
            self._splice_block_events(times, ntx, gas_used, height0,
                                      markers)

    def _pack(self, times: np.ndarray, n_vis: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray, int]:
        """Pack the consolidated mempool into the deferred blocks, stamp
        confirm times, append the ``BlockStats`` and dispatch handlers.
        Returns per-block tx counts, gas used and the first new height.

        The packer gets only the unconfirmed tail ``[_ptr, _n)``, its gas
        cumsum rebased to start at 0: ``_tmax`` is the running max over
        the whole history, so searching the tail equals searching it all
        clamped at ``_ptr``.  Its stops come back shifted by ``_ptr``;
        everything after the call is on absolute indices."""
        from repro.kernels.factory import get_kernel
        chain = self.chain
        nblk = times.shape[0]
        ptr0, n = chain._ptr, chain._n
        base = chain._gcum[ptr0 - 1] if ptr0 else 0
        stops = ptr0 + np.asarray(get_kernel("block_pack")(
            chain._tmax[ptr0:n], chain._gcum[ptr0:n] - base, times,
            np.maximum(n_vis - ptr0, 0), chain.block_gas_limit, 0),
            np.int64)
        starts = np.concatenate([[ptr0], stops[:-1]])
        if n:
            gend = np.where(stops > 0,
                            chain._gcum[np.maximum(stops - 1, 0)], 0)
            gprev = np.where(starts > 0,
                             chain._gcum[np.maximum(starts - 1, 0)], 0)
            gas_used = np.where(stops > starts, gend - gprev, 0)
        else:                                  # empty mempool: empty blocks
            gas_used = np.zeros(nblk, np.int64)
        ntx = stops - starts
        final = int(stops[-1])
        if final > ptr0:
            chain._confirm[ptr0:final] = np.repeat(times, ntx)
        dispatch = bool(chain._batch_handlers or chain._state_handlers)
        assert chain.quorum(chain.n_validators - chain.n_validators // 3)
        height0 = len(chain.blocks)
        parent = chain.blocks[-1].block_hash
        for b in range(nblk):
            lo, hi = int(starts[b]), int(stops[b])
            if dispatch and hi > lo:
                self._dispatch_handlers(lo, hi)
            blk = BlockStats(height0 + b, float(times[b]), int(ntx[b]),
                             int(gas_used[b]), lo, hi, parent)
            parent = blk.block_hash
            chain.blocks.append(blk)
        chain.total_gas += int(gas_used.sum())
        chain._ptr = final
        return ntx, gas_used, height0

    def _dispatch_handlers(self, lo: int, hi: int) -> None:
        """Per-(block, fn) handler dispatch — produce_block's contract,
        on one deferred block's confirmed slice."""
        chain = self.chain
        counts = np.bincount(chain._f[lo:hi], minlength=len(chain.fns))
        view = TxArrays(chain._t[lo:hi], chain._g[lo:hi],
                        chain._f[lo:hi], chain._s[lo:hi], chain.fns)
        for fid, h in chain._batch_handlers.items():
            if fid < counts.shape[0] and counts[fid]:
                h(chain.state, int(counts[fid]), view)
        for fid, h in chain._state_handlers.items():
            if fid < counts.shape[0] and counts[fid]:
                m = view.fn_id == fid
                h(chain.state_arrays,
                  TxArrays(view.submit_time[m], view.gas[m],
                           view.fn_id[m], view.sender_id[m], chain.fns))

    def _splice_block_events(self, times, ntx, gas_used, height0,
                             markers) -> None:
        """Land BlockPacked events at the positions the stepped path
        emitted them via ``EventLog.splice`` (the one sanctioned bulk-
        mutation path — rule R005; the log renumbers ``seq``)."""
        chain = self.chain
        inserts: List[Any] = []
        for pos, blo, bn in markers:
            run: List[Any] = []
            for b in range(blo, blo + bn):
                blk = chain.blocks[height0 + b]
                run.append(BlockPacked(
                    seq=-1, time=float(times[b]), shard=None,
                    height=blk.height, n_txs=int(ntx[b]),
                    gas_used=int(gas_used[b]), block_hash=blk.block_hash))
                chain._emit("block_packed", {
                    "height": blk.height, "n_txs": int(ntx[b]),
                    "gas_used": int(gas_used[b]),
                    "block_hash": blk.block_hash})
            inserts.append((pos, run))
        chain.events.splice(inserts)
