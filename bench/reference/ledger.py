"""Plain reference of what the AutoDFL ledger commits, from the traffic alone.

Imports nothing of the program under test and reads nothing it made.  It
states the node's observable semantics directly, in NumPy and hashlib:

  * fn ids: the four Table-I functions in the paper's order, then the
    three rollup settlement calls (``FN_ORDER``);
  * a tx word is (float32 submit-time bits, gas mod 2^32, fn id, sender);
    a digest is ``SEED ^ xor`` of ``mix(w) = (w ^ (w >> 16)) * MULT``
    over u32 words (mod 2^32);
  * a rollup shard seals its window's txs in arrival order into batches
    of ``batch_size``; a batch commits ``sum of base[f] over the fns in
    it + sum of count[f] * per_call[f]`` gas at the time of its last tx;
  * hash routing sends a tx to shard ``mix(sender) mod K``;
  * account state is six per-account fields, field-major in schema order;
    each Table-I tx adds 1 to one counter of its sender's row; the state
    root is sha256(schema header, word count, per-2048-word-chunk digests)
    and a shard's root commits only the rows it owns;
  * per window the L1 receives each shard's commits (time-sorted, in
    shard order), then one verify and one execute per shard that sealed,
    then packs one gas-limited FIFO block at the window's end;
  * a tx is settled once every L1 tx of its window is in a block.
"""
from __future__ import annotations

import hashlib
from typing import Dict, List, Sequence

import numpy as np

MIX_MULT = np.uint32(0x85EBCA6B)
MIX_SEED = np.uint32(0x9E3779B9)
CHUNK_WORDS = 2048
FN_ORDER = ("publishTask", "submitLocalModel", "calculateObjectiveRep",
            "calculateSubjectiveRep", "rollup_commit", "rollup_verify",
            "rollup_execute")
SCHEMA = (("balances", "<f8"), ("stake", "<f8"), ("reputation", "<f4"),
          ("tasks_published", "<i8"), ("submissions", "<i8"),
          ("rep_events", "<i8"))
#: which counter each Table-I fn id adds to
COUNTER_OF_FN = ("tasks_published", "submissions", "rep_events",
                 "rep_events")


def mix(words: np.ndarray) -> np.ndarray:
    w = np.asarray(words, np.uint32)
    return (w ^ (w >> np.uint32(16))) * MIX_MULT


def fold(words: np.ndarray) -> int:
    return int(MIX_SEED ^ np.bitwise_xor.reduce(mix(words))) \
        if np.size(words) else int(MIX_SEED)


def tx_words(t, gas, fn, sender) -> np.ndarray:
    w = np.empty((len(t), 4), np.uint32)
    w[:, 0] = np.asarray(t, np.float64).astype(np.float32).view(np.uint32)
    w[:, 1] = (np.asarray(gas, np.int64) & 0xFFFFFFFF).astype(np.uint32)
    w[:, 2] = np.asarray(fn, np.uint32)
    w[:, 3] = np.asarray(sender, np.uint32)
    return w


def owner(sender, n_shards: int) -> np.ndarray:
    return (mix(sender) % np.uint32(n_shards)).astype(np.int64)


def schema_header() -> bytes:
    return ";".join(f"{n}:{d}" for n, d in SCHEMA).encode()


def state_root(counters: Dict[str, np.ndarray], rows: np.ndarray,
               suffix: bytes = b"") -> str:
    """Root over the given account rows (all rows: the flat root)."""
    parts = []
    for name, dt in SCHEMA:
        col = counters.get(name)
        vals = (np.zeros(rows.size, dt) if col is None
                else col[rows].astype(dt))
        parts.append(vals.view(np.uint8))
    words = np.concatenate(parts).view(np.uint32)
    n = words.size
    padded = np.zeros(-(-n // CHUNK_WORDS) * CHUNK_WORDS, np.uint32)
    padded[:n] = words
    dig = MIX_SEED ^ np.bitwise_xor.reduce(
        mix(padded).reshape(-1, CHUNK_WORDS), axis=1)
    h = hashlib.sha256()
    h.update(schema_header() + suffix)
    h.update(np.uint64(n).tobytes())
    h.update(dig.astype("<u4").tobytes())
    return h.hexdigest()[:32]


def dirty_chunks(sender: np.ndarray, n_accounts: int) -> int:
    """State chunks one window's txs dirty: every field of each sender's
    row is rewritten when the root is refolded."""
    rows = np.unique(np.asarray(sender, np.int64))
    ids, off = [], 0
    for _, dt in SCHEMA:
        isw = np.dtype(dt).itemsize // 4
        ids.append((off + rows * isw) // CHUNK_WORDS)
        ids.append((off + rows * isw + isw - 1) // CHUNK_WORDS)
        off += n_accounts * isw
    return int(np.unique(np.concatenate(ids)).size)


class Ledger:
    """Replays windows of Table-I txs and records what the node should
    commit: per-shard batches, per-window update digests, sampled state
    roots, L1 blocks, and each tx's global batch id."""

    def __init__(self, gas: Dict, node: Dict, n_accounts: int,
                 sample_windows: Sequence[int] = ()):
        self.k = int(node["shards"])
        self.batch = int(node["batch_size"])
        self.block_time = float(node["block_time_s"])
        self.limit = int(node["block_gas_limit"])
        self.n_accounts = n_accounts
        tab = FN_ORDER[:4]
        self.base = np.array([gas["commit_base"][f] for f in tab], np.int64)
        self.per_call = np.array([gas["commit_per_call"][f] for f in tab],
                                 np.int64)
        self.verify = (gas["verify_single"], gas["verify_multi"])
        self.execute = (gas["execute_single"], gas["execute_multi"])
        self.samples = set(int(w) for w in sample_windows)
        self.counters = {n: np.zeros(n_accounts, np.int64)
                         for n in set(COUNTER_OF_FN)}
        self.batches: List[List[tuple]] = [[] for _ in range(self.k)]
        self.window_digests: List[List[int]] = [[] for _ in range(self.k)]
        self.roots: Dict[int, tuple] = {}
        self.tx_batch: List[np.ndarray] = []      # per window, per tx
        self.l1_t: List[np.ndarray] = []
        self.l1_g: List[np.ndarray] = []
        self.blocks: List[tuple] = []
        self.l1_end: List[int] = []               # per window
        self._n_l1 = 0
        self._ptr = 0
        self._cursor = 0.0
        self._window = 0
        self._owner_all = (owner(np.arange(n_accounts), self.k)
                           if self.k > 1 else None)

    def _seal(self, k: int, t, gas, fn, sender):
        """One shard's seal of one window: returns (commit times, commit
        gas, last time, n_batches, n_txs) and records batch rows."""
        n = len(t)
        bid = np.arange(n) // self.batch
        nb = int(bid[-1]) + 1
        counts = np.zeros((nb, 4), np.int64)
        np.add.at(counts, (bid, fn), 1)
        commit = (counts > 0) @ self.base + counts @ self.per_call
        starts = np.arange(nb) * self.batch
        now = np.maximum.reduceat(t, starts)
        words = tx_words(t, gas, fn, sender)
        mixed = mix(words.reshape(-1))
        dig = MIX_SEED ^ np.bitwise_xor.reduceat(mixed, starts * 4)
        ntx = counts.sum(axis=1)
        first = len(self.batches[k])
        self.batches[k].extend(zip(ntx.tolist(), commit.tolist(),
                                   dig.tolist()))
        self.window_digests[k].append(
            int(MIX_SEED ^ np.bitwise_xor.reduce(mixed)))
        post = np.argsort(now, kind="stable")
        return now[post], commit[post], float(now.max()), nb, n, first + bid

    def window(self, t, gas, fn, sender) -> None:
        """Apply one window's txs (arrival order, submit-time sorted)."""
        t = np.asarray(t, np.float64)
        fn = np.asarray(fn, np.int64)
        sender = np.asarray(sender, np.int64)
        gas = np.asarray(gas, np.int64)
        shard = owner(sender, self.k) if self.k > 1 else \
            np.zeros(len(t), np.int64)
        tx_batch = np.empty(len(t), np.int64)
        l1_t, l1_g, settle = [], [], []
        for k in range(self.k):
            m = shard == k
            if not m.any():
                continue
            now, commit, last, nb, n, bid = self._seal(
                k, t[m], gas[m], fn[m], sender[m])
            tx_batch[m] = bid
            l1_t.append(now)
            l1_g.append(commit)
            single = nb == 1 and n <= 5
            settle.append((last, self.verify[0 if single else 1],
                           self.execute[0 if single else 1]))
        for last, v, e in settle:
            l1_t.append(np.array([last, last]))
            l1_g.append(np.array([v, e], np.int64))
        self.tx_batch.append(tx_batch)
        for f, name in enumerate(COUNTER_OF_FN):
            np.add.at(self.counters[name], sender[fn == f], 1)
        if self._window in self.samples:
            self.roots[self._window] = self._roots()
        self.l1_t.extend(l1_t)
        self.l1_g.extend(l1_g)
        self._n_l1 += sum(x.size for x in l1_t)
        self.l1_end.append(self._n_l1)
        self._blocks(self._window + 1.0)
        self._window += 1

    def settled(self, w: int) -> bool:
        """Whether every L1 tx of window ``w`` is in a block."""
        return self.l1_end[w] <= self._ptr

    def _roots(self) -> tuple:
        rows = np.arange(self.n_accounts)
        flat = state_root(self.counters, rows)
        if self.k == 1:
            return (flat,)
        shard_roots = tuple(
            state_root(self.counters, np.flatnonzero(self._owner_all == k),
                       f"|shard={k}/{self.k}".encode())
            for k in range(self.k))
        h = hashlib.sha256()
        for r in shard_roots:
            h.update(r.encode())
        return (flat, h.hexdigest()[:32]) + shard_roots

    def _blocks(self, t_end: float) -> None:
        """FIFO blocks up to ``t_end``: each takes the longest prefix of
        the mempool whose txs are all due and whose gas fits the limit."""
        t = np.concatenate(self.l1_t) if self.l1_t else np.zeros(0)
        g = np.concatenate(self.l1_g) if self.l1_g else np.zeros(0, np.int64)
        self.l1_t, self.l1_g = [t], [g]
        tmax = np.maximum.accumulate(t) if t.size else t
        gcum = np.cumsum(g)
        while self._cursor < t_end:
            self._cursor += self.block_time
            ptr = self._ptr
            hi = max(int(np.searchsorted(tmax, self._cursor, "right")), ptr)
            base = int(gcum[ptr - 1]) if ptr else 0
            stop = ptr + int(np.searchsorted(gcum[ptr:hi], base + self.limit,
                                             "right"))
            used = int(gcum[stop - 1]) - base if stop > ptr else 0
            self.blocks.append((stop - ptr, used))
            self._ptr = stop


#: every comparison is exact
LIMITS = {"batches_wrong": 0, "window_digests_wrong": 0, "roots_wrong": 0,
          "blocks_wrong": 0, "receipts_wrong": 0}


def _list_diff(got: Sequence, want: Sequence) -> int:
    """Entries that differ, plus the difference in length."""
    return abs(len(got) - len(want)) + sum(
        1 for a, b in zip(got, want) if a != b)


def compare(got: Dict, ref: Ledger, windows: Sequence[int],
            picks: Sequence[tuple], receipts: Sequence[tuple]) -> List:
    """The numbers that decide ``correct``: (name, value, limit).

    ``got`` holds per-shard batch rows (n_txs, commit gas, digest) and
    per-window update digests, window roots by window index, and L1
    blocks (n_txs, gas used); ``receipts`` the (shard, batch, status)
    read back for each sampled (window, tx index) in ``picks``: right
    when finalized, in the reference's batch, and settled in an L1
    block by the reference's blocks (which the program's must equal)."""
    batches = sum(_list_diff(g, r) for g, r in
                  zip(got["batches"], ref.batches)) + \
        sum(len(g) for g in got["batches"][len(ref.batches):])
    digests = sum(_list_diff(g, r) for g, r in
                  zip(got["window_digests"], ref.window_digests))
    roots = 0
    for w in windows:
        want = ref.roots[w]
        have = got["roots"].get(w, ())
        roots += sum(1 for i, r in enumerate(want)
                     if i >= len(have) or have[i] != r)
    blocks = _list_diff(got["blocks"], ref.blocks)
    wrong = 0
    for (w, i), (shard, batch, status) in zip(picks, receipts):
        if status != "finalized" or batch != int(ref.tx_batch[w][i]) \
                or not ref.settled(w):
            wrong += 1
    values = {"batches_wrong": batches, "window_digests_wrong": digests,
              "roots_wrong": roots, "blocks_wrong": blocks,
              "receipts_wrong": wrong}
    return [(k, int(v), LIMITS[k]) for k, v in values.items()]
