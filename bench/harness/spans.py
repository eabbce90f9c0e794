"""The program's own spans and counters, per window.

The node opens ``repro.obs`` spans at its layer boundaries (``ledger.pool``,
``ledger.seal``, ``ledger.commit``, ``ledger.prove``, ``ledger.pack``,
``ledger.events``, ``ledger.kernel.<op>``), nested inside the benchmark's
``ledger.execute``, and counts windows, kernel calls, host-to-device
bytes, packed rows and moved events.  A span's **self time** is its
duration clipped to the window less the union of the loaded spans nested
inside it, so the self times of nested spans add up without counting an
interval twice.

On a program without these spans or counters every reader gets ``None``:
the metric is left out of the result line.
"""
from __future__ import annotations

import re
from typing import List, Optional, Sequence

from harness.trace import Event, clip, union


def self_ns(spans: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """``(name, start, self ns)`` of every span clipped to ``[lo, hi)``.
    A span nests inside the innermost earlier span that contains it."""
    ev = sorted(clip(spans, lo, hi), key=lambda e: (e[1], -e[2]))
    kids: List[List] = [[] for _ in ev]
    open_: List[int] = []
    for i, (_, s, d) in enumerate(ev):
        open_ = [j for j in open_ if ev[j][1] + ev[j][2] > s]
        parent = next((j for j in reversed(open_)
                       if ev[j][1] + ev[j][2] >= s + d), None)
        if parent is not None:
            kids[parent].append(("", s, d))
        open_.append(i)
    return [(n, s, d - sum(b - a for a, b in union(kids[i])))
            for i, (n, s, d) in enumerate(ev)]


def _matching(run, pattern: str) -> List[Event]:
    rx = re.compile(pattern)
    lo, hi = run.trace.window
    return [e for e in self_ns(run.trace.spans, lo, hi) if rx.search(e[0])]


def self_ms(run, pattern: str) -> Optional[float]:
    """Self ms per window of the spans whose name matches ``pattern``."""
    ev = _matching(run, pattern)
    if not ev or not run.steps:
        return None
    return sum(d for _, _, d in ev) / run.steps / 1e6


def spans_per_window(run, pattern: str) -> Optional[float]:
    """Spans per window whose name matches ``pattern``."""
    n = len(_matching(run, pattern))
    return n / run.steps if n and run.steps else None


def counter_per_window(prefix: str) -> Optional[float]:
    """Sum of the ``repro.obs`` counters named ``prefix`` or starting with
    ``prefix + "."``, over the ``windows`` counter: a mean over every
    window the process executed, warm-up windows included."""
    try:
        from repro import obs
    except ImportError:
        return None
    c = obs.counters()
    if not c.get("windows"):
        return None
    total = sum(v for k, v in c.items()
                if k == prefix or k.startswith(prefix + "."))
    return total / c["windows"]
