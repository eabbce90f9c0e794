"""The trace reducer on a hand-built trace."""
import pytest

from harness import trace as tr


def _trace():
    # window [0, 100); two chips; host spans record/execute per step
    mods = {
        "/device:TPU:0": [("jit__pack_scan(1)", 10, 10),
                          ("jit__fold_pallas_call(2)", 15, 10),
                          ("jit_gather(3)", 60, 5),
                          ("jit__pack_scan(1)", 95, 10)],   # runs past end
        "/device:TPU:1": [("jit__lane_fold_jit(4)", 40, 20)],
    }
    spans = [("bench.window", 0, 100),
             ("ledger.record", 0, 30), ("ledger.execute", 30, 20),
             ("ledger.record", 50, 20), ("ledger.execute", 70, 30)]
    h2d = [("XlaLinearize", 5, 4), ("H2D Dispatch", 7, 4),
           ("XlaLinearize", 98, 10)]
    return tr.Trace((0, 100), mods, spans, h2d)


def test_union_and_busy_clip_to_the_window():
    t = _trace()
    ev = t.modules["/device:TPU:0"]
    assert tr.union(tr.clip(ev, 0, 100)) == [(10, 25), (60, 65), (95, 100)]
    assert tr.busy_ns(ev, 0, 100) == 15 + 5 + 5
    assert tr.busy_ns(t.h2d, 0, 100) == 6 + 2


def test_idle_share_averages_the_chips():
    t = _trace()
    # chip 0 busy 25 ns, chip 1 busy 20 ns -> 22.5 ns mean
    assert tr.device_busy_s(t) == pytest.approx(22.5e-9)


def test_gaps_and_their_attribution_to_spans():
    t = _trace()
    g = tr.gaps(t.modules["/device:TPU:0"], 0, 100)
    assert g == [(0, 10), (25, 60), (65, 95)]
    spans = [s for s in t.spans if s[0] != "bench.window"]
    by = tr.gap_attribution(spans, g)
    # (0,10)->record 10; (25,60)->record 5, execute 20, record 10;
    # (65,95)->record 5, execute 25
    assert by == {"ledger.record": 30, "ledger.execute": 45}
    assert tr.gap_attribution([], [(0, 5)]) == {"between spans": 5}


def test_kernel_time_by_name_and_breakdown():
    t = _trace()
    ev = t.modules["/device:TPU:0"]
    assert tr.time_matching(ev, r"^jit__pack_scan\(", 0, 100) == 15
    b = tr.breakdown(t)
    assert [n for n, _ in b["device_ops"]][:2] == ["jit__lane_fold_jit",
                                                   "jit__pack_scan"]
    assert dict(b["device_ops"])["jit__pack_scan"] == pytest.approx(15e-9)
    idle = dict(b["idle_gaps"])
    assert sum(idle.values()) == pytest.approx((75 + 80) / 2 * 1e-9)
