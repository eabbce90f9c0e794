"""Program spans and counters: one tracing system for the node.

``span(name)`` opens a ``jax.profiler.TraceAnnotation``: with a profiler
session open it lands in the profiler's host plane, on the clock the
device events use, so each device idle gap can be put down to the
innermost program span open over it; with none open it costs about a
microsecond.  Spans cover phases of the window loop (pool, seal,
commit, prove, pack, events, each kernel call), never single
transactions.

Counters are process-wide integers, always on: ``count(name, n)`` adds,
``counters()`` returns a snapshot, ``reset()`` clears them (tests).
The node service reports them under ``"node"`` in its metrics, and the
benchmark's per-layer readers divide them by ``windows``.

Neither reads a clock (rule R003): the profiler stamps the spans.
"""
from __future__ import annotations

from typing import Dict

from jax.profiler import TraceAnnotation

_COUNTS: Dict[str, int] = {}


def span(name: str) -> TraceAnnotation:
    """Context manager: one named host span on the profiler's trace."""
    return TraceAnnotation(name)


def count(name: str, n: int = 1) -> None:
    _COUNTS[name] = _COUNTS.get(name, 0) + int(n)


def counters() -> Dict[str, int]:
    return dict(_COUNTS)


def reset() -> None:
    _COUNTS.clear()
