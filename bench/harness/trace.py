"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
``Trace`` of plain tuples: per device, the XLA modules (one event per
program run); on the host, the benchmark's own spans
(``jax.profiler.TraceAnnotation``, on the same clock as the device) and
the runtime's host-to-device staging events.  Everything else here works
on those tuples, so the tests can build a ``Trace`` by hand.

Times are nanoseconds.  ``window`` is the span named ``bench.window``:
the measured window, inside which busy time, idle gaps and per-window
means are taken.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

WINDOW_SPAN = "bench.window"
#: host events in which the runtime stages a host-to-device copy
H2D_EVENTS = ("XlaLinearize", "H2D Dispatch", "tpu::System::TransferToDevice")
#: host span prefixes the benchmark's drivers use
SPAN_PREFIXES = ("bench.", "ledger.", "fabric.", "fl.")


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    modules: Dict[str, List[Event]]       # device plane -> XLA Modules
    spans: List[Event]                    # benchmark host spans
    h2d: List[Event]                      # host-to-device staging

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    h2d: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = [(e.name, e.start_ns,
                                            e.duration_ns)
                                           for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, e.start_ns, e.duration_ns))
                    elif e.name in H2D_EVENTS:
                        h2d.append((e.name, e.start_ns, e.duration_ns))
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    w = max(win, key=lambda s: s[2])
    return Trace((w[1], w[1] + w[2]), modules, spans, h2d)


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by the events."""
    iv = sorted((s, s + d) for _, s, d in events if d > 0)
    out: List[List[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def gaps(events: Iterable[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of [lo, hi) not covered by the events."""
    out, cur = [], lo
    for a, b in union(clip(events, lo, hi)):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        out.append((cur, hi))
    return out


def gap_attribution(spans: Sequence[Event],
                    idle: Sequence[Tuple[float, float]],
                    outside: str = "between spans") -> Dict[str, float]:
    """Idle ns per host span.  ``spans`` are the benchmark's leaf spans,
    which follow one another without nesting; each piece of a gap goes to
    the span open over it, or to ``outside``."""
    leaf = sorted((s, s + d, n) for n, s, d in spans)
    starts = [a for a, _, _ in leaf]
    out: Dict[str, float] = {}
    for a, b in idle:
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(leaf) and leaf[i][0] < b:
            s0, s1, name = leaf[i]
            x, y = max(a, s0), min(b, s1)
            if y > x:
                out[name] = out.get(name, 0.0) + (y - x)
                covered += y - x
            i += 1
        if b - a > covered:
            out[outside] = out.get(outside, 0.0) + (b - a - covered)
    return out


def time_matching(events: Iterable[Event], pattern: str,
                  lo: float, hi: float) -> float:
    """Summed duration (ns, clipped to the window) of events whose name
    matches ``pattern`` (a regular expression, searched)."""
    rx = re.compile(pattern)
    return sum(d for n, _, d in clip(events, lo, hi) if rx.search(n))


def module_name(name: str) -> str:
    """``jit__pack_scan(5091...)`` -> ``jit__pack_scan``."""
    return name.split("(", 1)[0]


def device_busy_s(tr: Trace) -> float:
    """Busy seconds in the window, averaged over the traced chips."""
    if not tr.modules:
        return 0.0
    lo, hi = tr.window
    return sum(busy_ns(ev, lo, hi) for ev in tr.modules.values()) \
        / len(tr.modules) / 1e9


def breakdown(tr: Trace, top: int = 10) -> Dict[str, List]:
    """Device programs that took the most time (seconds, summed over the
    chips) and idle time by the benchmark span the host was in (seconds,
    averaged over the chips)."""
    lo, hi = tr.window
    by_mod: Dict[str, float] = {}
    for ev in tr.modules.values():
        for n, _, d in clip(ev, lo, hi):
            key = module_name(n)
            by_mod[key] = by_mod.get(key, 0.0) + d / 1e9
    idle: Dict[str, float] = {}
    inner = [s for s in tr.spans if s[0] != WINDOW_SPAN]
    for ev in tr.modules.values():
        for k, v in gap_attribution(inner, gaps(ev, lo, hi)).items():
            idle[k] = idle.get(k, 0.0) + v / 1e9 / len(tr.modules)
    rank = lambda d: [[k, v] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_mod), "idle_gaps": rank(idle)}
