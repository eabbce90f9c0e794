"""Traffic: a mix file's parameters -> a cell's inputs, from a seed.

A mix is a data file ``traffic/<mix>.json``.  Its ``generator`` key names
the generator, ``generators/<generator>.py``, which exposes
``generate(mix, seed, registry, **kw)``; everything else in the file is a
parameter.  A generator draws per-tx senders through a sender law named
by the mix, ``senders/<law>.py`` exposing ``draw(rng, n, n_accounts,
**params)``.  New shapes of traffic are new files; none is edited.

The same seed gives the same inputs: every draw comes from ``numpy``
generators seeded with the run's ``--seed`` (any whole number).
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator per (seed, stream); streams keep the traffic's draws
    apart from the benchmark's other seeded choices."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), stream])


def sender_law(mix: Dict) -> Tuple[str, Dict]:
    """``"senders": "uniform"`` or ``{"law": "zipf", "theta": 0.99}`` ->
    (law name, its parameters)."""
    s = mix["senders"]
    if isinstance(s, str):
        return s, {}
    params = dict(s)
    return params.pop("law"), params


def generate(mix: Dict, seed: int, registry, **kw):
    gen = registry.module("generators", mix["generator"])
    return gen.generate(mix, seed, registry, **kw)
