"""The FL cell at a CPU size: LeNet-5 at its published widths, 2 tasks x
4 trainers, 2 rounds, a small validation set.  The timed path agrees with
``reference/fl.py``; each fault the check must see is caught (a program
run with the fault, or the records a faulty program would have written);
a traced run reports every FL per-layer metric."""
import time

import pytest

from harness import core
from harness import fl_costs
from harness.registry import Registry
from harness.trace import WINDOW_SPAN, Trace

CELL = "fl-wide-t4n128"
SMALL = {"config": {"n_trainers": 4, "n_select": 4,
                    "data": {"images_per_trainer": 16,
                             "validation_images": 20, "n_oracles": 5}},
         "traffic": {"tasks_per_epoch": 2, "rounds": 2, "batch": 4,
                     "warmup": {"min_epochs": 1, "stable_epochs": 1,
                                "max_epochs": 2},
                     "max_epochs_per_s": 2, "replay_window": 2}}
SPAN_METRICS = ("fl.train_ms", "fl.score_ms", "fl.aggregate_ms",
                "fl.emit_ms", "fl.settle_ms")
DEVICE_METRICS = ("fl.train_mfu", "fl.score_mfu", "fl.agg_roofline",
                  "device_idle.fl")
#: which check each fault must fail
FAULTS = {"bf16_state": "submissions_wrong", "no_dp": "submissions_wrong",
          "unsettled_tx": "counts_wrong", "drop_update": "merges_wrong",
          "uniform_merge": "merges_wrong",
          "malicious_as_good": "scores_wrong"}


@pytest.fixture(scope="module")
def registry():
    return Registry()


def _driver(registry, seed=7, fault=None, epochs=2):
    cfg = registry.config("lenet5-fl")
    mix = registry.traffic("fl-t4n128-r3")
    cfg.update(SMALL["config"])
    mix.update(SMALL["traffic"])
    if fault:
        cfg["fault"] = fault
    drv = registry.module("drivers", "fl").Driver(
        cfg, mix, seed, 0.5, core.devices_for(1, False), registry)
    drv.setup()
    for _ in range(epochs):
        drv.step()
    return drv


def _wrong(checks):
    return {k: v for k, v, lim in checks if v > lim}


@pytest.fixture(scope="module")
def driver(registry):
    return _driver(registry)


def test_program_agrees_with_the_reference(driver):
    assert _wrong(driver.check()) == {}
    attempted, failed = driver.window_counts()
    assert attempted > 0 and failed == 0
    # every round window went through the megastep
    assert all(x["mega"] == driver.rounds for x in driver.log)


@pytest.mark.parametrize("fault", ["drop_update", "uniform_merge",
                                   "malicious_as_good"])
def test_faulty_records_are_caught(driver, fault):
    driver.fault = fault
    try:
        wrong = _wrong(driver.check())
    finally:
        driver.fault = None
    assert wrong.get(FAULTS[fault], 0) > 0, wrong


@pytest.mark.parametrize("fault", ["bf16_state", "no_dp", "unsettled_tx"])
def test_faulty_program_is_caught(registry, fault):
    drv = _driver(registry, fault=fault)
    wrong = _wrong(drv.check())
    assert wrong.get(FAULTS[fault], 0) > 0, wrong
    if fault == "unsettled_tx":
        assert drv.window_counts()[1] > 0


def test_traced_run_reports_every_fl_metric(registry):
    res = core.run_cell(CELL, 11, 0.5, True, time.perf_counter(),
                        require_tpu=False, registry=registry,
                        overrides=SMALL)
    assert res["correct"], res["checks"]
    assert set(SPAN_METRICS) <= set(res["metrics"]), res["metrics"]
    assert all(res["metrics"][m]["value"] > 0 for m in SPAN_METRICS)


def test_device_metrics_read_the_named_programs(registry, driver):
    """The CPU has no device plane, so the device-trace readers get a
    trace with each FL program's events put in by hand, beside the
    counters and records of a real run; shares stay under 100%."""
    driver.measuring, driver._obs0 = True, {}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    mods = [(name + "(1)", 10.0 + 1e6 * i, 5e5)
            for i, name in enumerate(n for names in fl_costs.PROGRAMS.values()
                                     for n in names)]
    tr = Trace((0.0, 2e7), {"/device:TPU:0": mods},
               [(WINDOW_SPAN, 0.0, 2e7)], [])
    run = core.Run(tr, driver, peaks, 1, 2, 0.02)
    for name in DEVICE_METRICS + ("fl.step_mfu",):
        value = registry.module("metrics", name).read(run)
        assert value is not None and 0 < value < 100, (name, value)
    # a trace without the programs reads nothing
    empty = core.Run(Trace((0.0, 2e7), {}, [], []), driver, peaks, 1, 2,
                     0.02)
    for name in DEVICE_METRICS:
        assert registry.module("metrics", name).read(empty) is None
