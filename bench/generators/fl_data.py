"""FL traffic: the cell's images, IID partitions and per-round draws.

The data set is ``repro.data.synthetic.make_mnist_like`` (32x32x1, ten
classes) drawn from the run's seed: ``n_trainers * images_per_trainer``
training images, trainer ``i`` owning rows ``[i * per, (i + 1) * per)``,
then ``validation_images`` for the DON.  Every other draw of a run is a
function of (seed, epoch, task[, round]), so the plain reference makes
the same draws from the same numbers:

  * ``task_seeds``: the task's cohort seed (participation and DP/fake
    keys) and its ``init_seed`` (the global model it starts from);
  * ``batch_rows``: per trainer and local step, the ``batch`` rows of its
    own partition it trains on in that round (with replacement).

The mix's ``senders`` names the law the protocol's own emission follows:
every selected trainer sends its round's transactions, so the senders
are uniform over the cohort; the protocol picks them, no draw does.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

#: stream ids that keep this generator's draws apart from one another
DATA, TASK, BATCH = 11, 12, 13


@dataclasses.dataclass
class FLData:
    train_x: np.ndarray      # (n_trainers * per, 32, 32, 1) float32
    train_y: np.ndarray      # (n_trainers * per,) int32
    val_x: np.ndarray        # (n_val, 32, 32, 1) float32
    val_y: np.ndarray        # (n_val,) int32
    per: int                 # images per trainer


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([abs(int(seed)), int(seed < 0)]
                                 + [int(s) for s in stream])


def generate(mix, seed: int, registry, *, n_trainers: int, per: int,
             n_val: int) -> FLData:
    from repro.data.synthetic import make_mnist_like
    n = n_trainers * per
    xs, ys = make_mnist_like(n + n_val,
                             seed=int(_rng(seed, DATA).integers(2 ** 62)))
    return FLData(xs[:n], ys[:n], xs[n:], ys[n:], per)


def task_seeds(seed: int, epoch: int, task: int) -> Tuple[int, int]:
    """(cohort seed, init seed) of task ``task`` of epoch ``epoch``."""
    a, b = _rng(seed, TASK, epoch, task).integers(0, 2 ** 31, 2)
    return int(a), int(b)


def batch_rows(seed: int, epoch: int, task: int, rnd: int, n_trainers: int,
               steps: int, batch: int, per: int) -> np.ndarray:
    """(n_trainers, steps, batch) global training rows of one round."""
    idx = _rng(seed, BATCH, epoch, task, rnd).integers(
        0, per, (n_trainers, steps, batch))
    return np.arange(n_trainers)[:, None, None] * per + idx
