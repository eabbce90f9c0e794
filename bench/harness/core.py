"""The harness core: one cell, one process, one result line.

``run_cell`` builds the cell's driver, times its set-up, drives it for
``seconds`` (closed loop: the next unit starts when the last one ends),
optionally under the profiler, then checks what the timed path produced
against the plain reference and returns the result.  The end-to-end
arithmetic lives here once:

  * ``<rate metric>``: units completed in the window over the window's
    wall seconds (all the work and all the time);
  * ``<tail metric>``: 95th percentile of every unit's wall time;
  * ``setup_s``: process start to the window's start (imports, device
    init, state, traffic, compile-cache reads, warm-up).

A driver (``drivers/<name>.py``) defines ``Driver(cfg, mix, seed,
seconds, devices, registry)`` with ``RATE_METRIC`` and ``TAIL_METRIC``
names, ``setup()``, ``step() -> units completed``, ``window_counts() ->
(attempted, failed)`` and ``check(control=False) -> [(name, value,
limit)]``; optionally ``end_to_end() -> {metric: value}`` for further
end-to-end metrics it measures itself.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

from harness import trace as tracing
from harness.registry import ROOT, Registry

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Probe:
    """Compiles and persistent-cache hits, from JAX's own events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1


def devices_for(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else the fixed ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def percentile(values: List[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default rule)."""
    v = sorted(values)
    if not v:
        return float("nan")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


class Run:
    """What a per-layer metric reader sees of one traced run."""

    def __init__(self, trace, driver, peaks, units, steps, window_s):
        self.trace = trace
        self.driver = driver
        self.peaks = peaks
        self.units = units
        self.steps = steps
        self.window_s = window_s


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, require_tpu: bool = True,
             registry: Optional[Registry] = None,
             overrides: Optional[Dict] = None,
             with_control: bool = False) -> Dict:
    """Run one cell; returns the result object (see ``run.py``).
    ``overrides`` (tests only) update the configuration and traffic
    dictionaries, e.g. to shrink a cell to a CPU-sized copy.
    ``with_control`` (``control.py`` only) adds ``control_checks``: the
    same comparison with the control in the program's place."""
    reg = registry or Registry()
    cell = reg.cell(cell_name)
    cfg = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    for key, upd in (overrides or {}).items():
        {"config": cfg, "traffic": mix}[key].update(upd)
    devs = devices_for(int(cell["chips"]), require_tpu)
    dev = devs[0]
    peaks_all = json.loads((reg.dir / "peaks.json").read_text())
    if require_tpu and dev.device_kind not in peaks_all:
        raise NoChip(f"no peaks for device kind {dev.device_kind!r}")
    peaks = peaks_all.get(dev.device_kind, {})
    cache = enable_compile_cache() if require_tpu else "off"
    say(f"{dev.platform} {dev.device_kind} x{len(devs)}; compile cache "
        f"{cache}")
    probe = Probe()
    driver = reg.module("drivers", cfg["driver"]).Driver(
        cfg, mix, seed, seconds, devs, reg)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    say(f"setup_s={setup_s:.3f} compiles={probe.compiles} "
        f"compile_s={probe.compile_s:.3f} cache_hits={probe.cache_hits}")

    import jax
    c0 = probe.compiles
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    durations: List[float] = []
    units = 0
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        now = t0
        while now < deadline:
            units += driver.step()
            t1 = time.perf_counter()
            durations.append(t1 - now)
            now = t1
    window_s = now - t0
    if trace:
        jax.profiler.stop_trace()
    in_window = probe.compiles - c0
    say(f"window_s={window_s:.3f} steps={len(durations)} units={units} "
        f"compiles_in_window={in_window}")
    tenth = max(1, len(durations) // 10)
    first = percentile(durations[:tenth], 50) * 1e3
    last = percentile(durations[-tenth:], 50) * 1e3
    say(f"step_ms median first tenth={first:.3f} last tenth={last:.3f}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)

    attempted, failed = driver.window_counts()
    checks = driver.check()
    correct = all(v <= lim for _, v, lim in checks) and failed == 0

    if trace:
        metrics, extra = _per_layer(reg, cell_name, tdir, driver, peaks,
                                    units, len(durations), window_s)
    else:
        candidates = {driver.RATE_METRIC: units / window_s,
                      driver.TAIL_METRIC:
                          percentile(durations, 95.0) * 1e3,
                      "setup_s": setup_s}
        if hasattr(driver, "end_to_end"):
            candidates.update(driver.end_to_end())
        metrics, extra = {}, {}
        for m in reg.metrics_for(cell_name, "end_to_end"):
            metrics[m["name"]] = {"value": candidates[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    device.update(extra.pop("device", {}))
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    result.update(extra)
    if with_control:
        result["control_checks"] = {name: v for name, v, _ in
                                    driver.check(control=True)}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    if tdir:
        shutil.rmtree(tdir, ignore_errors=True)
    return result


def _per_layer(reg, cell_name, tdir, driver, peaks, units, steps,
               window_s):
    import glob
    files = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                             recursive=True))
    tr = tracing.load(files[-1])
    run = Run(tr, driver, peaks, units, steps, window_s)
    metrics = {}
    for m in reg.metrics_for(cell_name, "per_layer"):
        value = reg.module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    extra = {"device": {"busy_s": tracing.device_busy_s(tr),
                        "window_s": tr.window_ns / 1e9},
             "breakdown": tracing.breakdown(tr)}
    return metrics, extra


def main(args, t_start: float) -> int:
    try:
        result = run_cell(args.workload, args.seed, float(args.seconds),
                          bool(args.trace), t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
