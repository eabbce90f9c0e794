"""Cells, configurations, mixes and metrics are found by name: a new one
is a new file, and no existing file needs an edit."""
import json
import shutil

import pytest

from harness import traffic
from harness.registry import BENCH_DIR, ROOT, Registry


def test_benchmark_json_names_only_files_that_exist():
    reg = Registry()
    spec = reg.spec
    names = {c["name"] for c in spec["configs"]}
    for cell in spec["workloads"]:
        assert cell["config"] in names
        cfg = reg.config(cell["config"])
        assert (BENCH_DIR / "drivers" / f"{cfg['driver']}.py").exists()
        assert (BENCH_DIR / "reference" / f"{cfg['reference']}.py").exists()
        mix = reg.traffic(cell["traffic"])
        assert (BENCH_DIR / "generators" / f"{mix['generator']}.py").exists()
        assert (BENCH_DIR / "senders"
                / f"{traffic.sender_law(mix)[0]}.py").exists()
    for c in spec["configs"]:
        assert (ROOT / c["file"]).exists()
    for m in spec["per_layer"]:
        assert hasattr(reg.module("metrics", m["name"]), "read")
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in spec["workloads"]}
    moves = {m["moves"] for m in spec["per_layer"]}
    assert moves <= {m["name"] for m in spec["end_to_end"]}


@pytest.fixture
def bench_copy(tmp_path):
    dst = tmp_path / "bench"
    shutil.copytree(BENCH_DIR, dst,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_new_config_mix_and_metric_are_picked_up(bench_copy):
    b = bench_copy / "bench"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    cfg = json.loads((b / "configs" / "autodfl-ledger-1m.json").read_text())
    cfg["accounts"] = 1 << 16
    (b / "configs" / "autodfl-ledger-64k.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "table1-uniform-1k.json").read_text())
    mix["rate_per_s"] = 500
    (b / "traffic" / "table1-uniform-500.json").write_text(json.dumps(mix))
    # a mix with a sender law no file had: a new law file and a new mix
    (b / "senders" / "hotset.py").write_text(
        "def draw(rng, n, n_accounts, hot=8):\n"
        "    return rng.integers(0, hot, n)\n")
    mix["senders"] = {"law": "hotset", "hot": 4}
    (b / "traffic" / "table1-hotset-500.json").write_text(json.dumps(mix))
    (b / "metrics" / "ledger.window_count.py").write_text(
        "def read(run):\n    return float(run.steps)\n")
    spec = json.loads((bench_copy / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "ledger-small", "chips": 1,
                              "config": "autodfl-ledger-64k",
                              "traffic": "table1-uniform-500", "why": "t"})
    spec["per_layer"].append({"name": "ledger.window_count", "unit": "n",
                              "better": "higher", "source": "host_clock",
                              "layer": "window loop record",
                              "moves": "ledger_tx_per_s",
                              "workloads": ["ledger-small"]})
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(spec))

    reg = Registry(b)
    cell = reg.cell("ledger-small")
    assert reg.config(cell["config"])["accounts"] == 1 << 16
    assert reg.traffic(cell["traffic"])["rate_per_s"] == 500
    gas = json.loads((b / "reference" / "table1_gas.json").read_text())
    hot = traffic.generate(reg.traffic("table1-hotset-500"), 9, reg,
                           n_windows=4, n_accounts=1 << 10,
                           l1_gas=gas["l1_per_call"])
    assert len(hot.sender) > 1000 and set(hot.sender.tolist()) <= {0, 1, 2, 3}
    assert [m["name"] for m in reg.metrics_for("ledger-small",
                                               "per_layer")] == \
        ["ledger.window_count"]
    assert reg.module("metrics", "ledger.window_count").read(
        type("R", (), {"steps": 7})()) == 7.0
    assert "setup_s" in {m["name"] for m in
                         reg.metrics_for("ledger-small", "end_to_end")}
    # no file that was there before changed
    assert all(p.read_bytes() == data for p, data in before.items())


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        Registry().cell("no-such-cell")
