"""Senders uniform over every account."""
import numpy as np


def draw(rng: np.random.Generator, n: int, n_accounts: int) -> np.ndarray:
    return rng.integers(0, n_accounts, n)
