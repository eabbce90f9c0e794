"""Self times of nested program spans, the per-layer readers over them
and over the program's counters, and a traced CPU-size run that reports
every one of them."""
import sys
import time

import pytest

from harness import core
from harness.registry import Registry
from harness.spans import self_ns
from harness.trace import Trace

SPAN_METRICS = ("ledger.pool_ms", "ledger.seal_ms", "ledger.commit_ms",
                "ledger.prove_ms", "ledger.pack_ms", "ledger.events_ms",
                "ledger.kernel_host_ms")
COUNTER_METRICS = ("ledger.h2d_mb", "ledger.pack_rows",
                   "ledger.events_moved")
NEW_METRICS = SPAN_METRICS + ("ledger.kernel_calls",) + COUNTER_METRICS


class _Run:
    def __init__(self, trace, steps):
        self.trace, self.steps = trace, steps


@pytest.fixture(scope="module")
def registry():
    return Registry()


@pytest.fixture
def fresh_obs():
    from repro import obs
    obs.reset()
    yield obs
    obs.reset()


def _read(registry, name, run):
    return registry.module("metrics", name).read(run)


def _trace():
    # window [0, 1000), two steps; the second window's execute runs past
    # the end, and on a fabric a lane's seal nests in the window's seal
    spans = [("bench.window", 0, 1000),
             ("ledger.record", 0, 100),
             ("ledger.execute", 100, 400),
             ("ledger.seal", 110, 200),
             ("ledger.kernel.batch_seal", 120, 30),
             ("ledger.commit", 160, 100),
             ("ledger.kernel.dirty_fold", 170, 60),
             ("ledger.prove", 320, 20),
             ("ledger.pool", 350, 10),
             ("ledger.pack", 360, 80),
             ("ledger.kernel.block_pack", 370, 40),
             ("ledger.events", 440, 50),
             ("ledger.read", 500, 10),
             ("ledger.execute", 600, 500),
             ("ledger.seal", 600, 300),
             ("ledger.seal", 650, 100),
             ("ledger.events", 950, 100)]
    return Trace((0, 1000), {}, spans, [])


def test_self_time_subtracts_the_nested_spans():
    got = {}
    for name, start, d in self_ns(_trace().spans, 0, 1000):
        got[(name, start)] = d
    assert got[("ledger.execute", 100)] == 400 - (200 + 20 + 10 + 80 + 50)
    assert got[("ledger.seal", 110)] == 200 - 30 - 100
    assert got[("ledger.commit", 160)] == 100 - 60
    assert got[("ledger.kernel.dirty_fold", 170)] == 60
    assert got[("ledger.pack", 360)] == 80 - 40
    # same-named nesting counts each interval once; clipped at the end
    assert got[("ledger.seal", 600)] == 300 - 100
    assert got[("ledger.seal", 650)] == 100
    assert got[("ledger.events", 950)] == 50
    assert got[("ledger.execute", 600)] == 400 - 300 - 50
    assert got[("bench.window", 0)] == 1000 - (100 + 400 + 10 + 400)


def test_kernel_in_commit_in_seal_in_execute(registry):
    """The layer readers split the first execute span exactly: its own
    self time plus the six layers and the kernels is its duration."""
    spans = [("bench.window", 0, 1000)] + [
        s for s in _trace().spans if s[1] < 500]
    run = _Run(Trace((0, 1000), {}, spans, []), steps=1)
    ms = {m: _read(registry, m, run) for m in SPAN_METRICS}
    assert ms["ledger.seal_ms"] == pytest.approx(70e-6)
    assert ms["ledger.commit_ms"] == pytest.approx(40e-6)
    assert ms["ledger.kernel_host_ms"] == pytest.approx((30 + 60 + 40) * 1e-6)
    assert ms["ledger.pack_ms"] == pytest.approx(40e-6)
    assert ms["ledger.events_ms"] == pytest.approx(50e-6)
    assert sum(ms.values()) == pytest.approx((400 - 40) * 1e-6)
    assert _read(registry, "ledger.execute_ms", run) == pytest.approx(400e-6)
    assert _read(registry, "ledger.kernel_calls", run) == 3


def test_span_readers_average_over_the_steps(registry):
    run = _Run(_trace(), steps=2)
    assert _read(registry, "ledger.seal_ms", run) == \
        pytest.approx((70 + 200 + 100) / 2 * 1e-6)
    assert _read(registry, "ledger.events_ms", run) == \
        pytest.approx((50 + 50) / 2 * 1e-6)
    assert _read(registry, "ledger.kernel_calls", run) == 1.5


def test_readers_find_nothing_on_a_program_without_spans(registry,
                                                         monkeypatch):
    import repro
    spans = [("bench.window", 0, 100), ("ledger.record", 0, 30),
             ("ledger.execute", 30, 60)]
    run = _Run(Trace((0, 100), {}, spans, []), steps=1)
    for m in SPAN_METRICS + ("ledger.kernel_calls",):
        assert _read(registry, m, run) is None, m
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for m in COUNTER_METRICS:
        assert _read(registry, m, run) is None, m


def test_counter_readers_are_means_over_windows(registry, fresh_obs):
    run = _Run(_trace(), steps=2)
    for m in COUNTER_METRICS:                  # no window executed yet
        assert _read(registry, m, run) is None, m
    fresh_obs.count("windows", 4)
    fresh_obs.count("kernel.h2d_bytes.dirty_fold", 4 * 46_137_344)
    fresh_obs.count("kernel.h2d_bytes.block_pack", 1_000_000)
    fresh_obs.count("kernel.calls.dirty_fold", 4)
    fresh_obs.count("pack.rows", 100)
    fresh_obs.count("events.moved", 16_400)
    assert _read(registry, "ledger.h2d_mb", run) == \
        pytest.approx(46.137344 + 0.25)
    assert _read(registry, "ledger.pack_rows", run) == 25
    assert _read(registry, "ledger.events_moved", run) == 4100


def test_traced_cpu_run_reports_every_new_metric(fresh_obs):
    """The cell at a CPU size, traced: every new metric is reported, the
    run stays correct, and the layers' self times fit in execute."""
    res = core.run_cell("ledger-mixed-uniform", 2**31 + 17, 0.3, True,
                        time.perf_counter(), require_tpu=False,
                        overrides={"config": {"accounts": 1 << 12},
                                   "traffic": {"rate_per_s": 200,
                                               "max_windows_per_s": 2000}})
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    missing = [k for k in NEW_METRICS if m.get(k) is None]
    assert not missing, m
    assert sum(m[k] for k in SPAN_METRICS) <= m["ledger.execute_ms"]
    assert m["ledger.kernel_calls"] >= 1 and m["ledger.pack_rows"] > 0
    assert m["ledger.events_moved"] > 0 and m["ledger.h2d_mb"] > 0
