"""Zipf senders (YCSB's request distribution): the account of rank ``r``
(0-based) is drawn with weight ``1 / (r + 1) ** theta``; ranks map to
accounts through a seeded permutation, so the hot accounts are spread
over the state rather than packed at its start."""
import numpy as np


def draw(rng: np.random.Generator, n: int, n_accounts: int,
         theta: float = 0.99) -> np.ndarray:
    w = 1.0 / np.arange(1, n_accounts + 1, dtype=np.float64) ** theta
    cdf = np.cumsum(w)
    rank = np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right")
    perm = rng.permutation(n_accounts)
    return perm[np.minimum(rank, n_accounts - 1)]
