"""Windows of Table-I txs: Poisson arrivals cut into modeled windows.

Parameters (from the mix file): ``rate_per_s`` (mean txs per modeled
second), ``window_s``, ``mix`` (function -> share), ``senders`` (a sender
law by name, with its parameters).  Every seed gets the same kinds of
windows: a Poisson count per window, uniform times inside it, functions
drawn from the shares, senders from the law.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence

import numpy as np

from harness.traffic import rng_for, sender_law


@dataclasses.dataclass
class LedgerWindows:
    """Window ``w`` is ``[offsets[w], offsets[w+1])`` of the arrays,
    submit-time sorted, all inside the modeled span ``[w, w+1) *
    window_s``."""

    t: np.ndarray            # float64 submit times
    gas: np.ndarray          # int64 per-tx L1 gas (Table I)
    fn: np.ndarray           # int32 fn id, order of ``fns``
    sender: np.ndarray       # int32 account id
    offsets: np.ndarray      # int64 (n_windows + 1,)
    fns: Sequence[str]
    window_s: float

    def __len__(self) -> int:
        return self.offsets.shape[0] - 1

    def window(self, w: int):
        lo, hi = int(self.offsets[w]), int(self.offsets[w + 1])
        return (self.t[lo:hi], self.gas[lo:hi], self.fn[lo:hi],
                self.sender[lo:hi])


def generate(mix: Dict, seed: int, registry, *, n_windows: int,
             n_accounts: int, l1_gas: Dict[str, int]) -> LedgerWindows:
    rng = rng_for(seed)
    ws = float(mix["window_s"])
    fns = list(mix["mix"])
    p = np.array([mix["mix"][f] for f in fns], np.float64)
    counts = rng.poisson(float(mix["rate_per_s"]) * ws, n_windows)
    total = int(counts.sum())
    w = np.repeat(np.arange(n_windows, dtype=np.float64), counts)
    t = np.sort((w + rng.random(total)) * ws)
    fn = rng.choice(len(fns), size=total, p=p / p.sum()).astype(np.int32)
    law, params = sender_law(mix)
    sender = registry.module("senders", law).draw(
        rng_for(seed, stream=2), total, n_accounts, **params)
    sender = np.asarray(sender, np.int64).astype(np.int32)
    gas = np.array([l1_gas[f] for f in fns], np.int64)[fn]
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    return LedgerWindows(t, gas, fn, sender, offsets, fns, ws)
