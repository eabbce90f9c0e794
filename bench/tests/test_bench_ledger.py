"""The ledger cells end to end at a CPU size: correct on the program,
not correct on the control, and not correct under each fault the timed
path can have.  The chip check is skipped; everything else is a run.

The four-chip fabric cell is not in ``BENCHMARK.json`` yet (it has not
been measured on the chip); its configuration, mix and driver path are
exercised through a registry whose spec adds the cell."""
import json
import time

import numpy as np
import pytest

from harness import core
from harness.registry import BENCH_DIR, ROOT, Registry

SMALL = {"config": {"accounts": 1 << 12},
         "traffic": {"rate_per_s": 200, "max_windows_per_s": 2000}}
FABRIC_CELL = {"name": "fabric4-mixed-uniform", "config": "autodfl-fabric4-1m",
               "traffic": "table1-uniform-1k", "chips": 4,
               "why": "4-shard fabric, shard_map lane fold"}


@pytest.fixture(scope="module")
def registry(tmp_path_factory):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = FABRIC_CELL["name"]
    if name not in {w["name"] for w in spec["workloads"]}:
        spec["workloads"].append(FABRIC_CELL)
        for m in spec["end_to_end"] + spec["per_layer"]:
            if "ledger-mixed-uniform" in m.get("workloads", ()):
                m["workloads"].append(name)
    path = tmp_path_factory.mktemp("spec") / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    return Registry(BENCH_DIR, benchmark_json=path)


def _run(registry, cell, seed=5, **kw):
    return core.run_cell(cell, seed, 0.3, False, time.perf_counter(),
                         require_tpu=False, registry=registry,
                         overrides=SMALL, **kw)


def _wrong(res):
    return {k: c["value"] for k, c in res["checks"].items()
            if c["value"] > c["limit"]}


@pytest.mark.parametrize("cell", ["ledger-mixed-uniform",
                                  "fabric4-mixed-uniform"])
def test_program_correct_control_not(registry, cell):
    res = _run(registry, cell, with_control=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    ctl = res["control_checks"]
    assert ctl["batches_wrong"] > 0 and ctl["roots_wrong"] > 0
    assert set(res["metrics"]) == {"ledger_tx_per_s",
                                   "ledger_window_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"


def _patch_kernel(monkeypatch, op, wrap):
    from repro.kernels import factory
    factory._load()
    real = factory._REGISTRY[op]["numpy"]
    monkeypatch.setitem(factory._REGISTRY[op], "numpy", wrap(real))


def test_state_left_unchanged_is_caught(registry, monkeypatch):
    from repro.core.engine import VectorRollup
    monkeypatch.setattr(VectorRollup, "_apply_state", lambda self, txs: None)
    res = _run(registry, "ledger-mixed-uniform")
    assert not res["correct"] and "roots_wrong" in _wrong(res)


def test_half_the_batch_left_out_is_caught(registry, monkeypatch):
    from repro.core.engine import TxArrays
    from repro.core.fused import FusedWindowLoop
    real = FusedWindowLoop.submit

    def submit(self, target, batch, shard=None):
        h = len(batch) // 2
        return real(self, target, TxArrays(
            batch.submit_time[:h], batch.gas[:h], batch.fn_id[:h],
            batch.sender_id[:h], batch.fns), shard)
    monkeypatch.setattr(FusedWindowLoop, "submit", submit)
    res = _run(registry, "ledger-mixed-uniform")
    assert not res["correct"] and "batches_wrong" in _wrong(res)


def test_altered_digest_is_caught(registry, monkeypatch):
    def wrap(real):
        def seal(words, starts):
            out = np.array(real(words, starts))
            out[0] ^= np.uint32(1)
            return out
        return seal
    _patch_kernel(monkeypatch, "batch_seal", wrap)
    res = _run(registry, "ledger-mixed-uniform")
    assert not res["correct"] and "batches_wrong" in _wrong(res)


def test_lane_exchange_left_out_is_caught(registry, monkeypatch):
    """The fabric's lane fold returns only the first chip's lanes."""
    def wrap(real):
        def seal(words, starts, n_seg, n_words):
            out = np.array(real(words, starts, n_seg, n_words))
            out[1:] = 0
            return out
        return seal
    _patch_kernel(monkeypatch, "shard_seal", wrap)
    res = _run(registry, "fabric4-mixed-uniform")
    assert not res["correct"] and "batches_wrong" in _wrong(res)


def test_fabric_cell_runs_four_shards(registry):
    cfg = registry.config(registry.cell("fabric4-mixed-uniform")["config"])
    assert cfg["node"]["shards"] == 4


def _node(registry, **upd):
    cfg = registry.config("autodfl-ledger-1m")
    return dict(cfg["node"], **upd)


def test_event_ring_wraps_and_stays_correct(registry):
    """A ring far smaller than the run's events: every window's events
    are still read by cursor before they are evicted."""
    res = core.run_cell("ledger-mixed-uniform", 8, 0.3, False,
                        time.perf_counter(), require_tpu=False,
                        registry=registry,
                        overrides={"config": {"accounts": 1 << 12,
                                              "node": _node(registry,
                                                            event_cap=64)},
                                   "traffic": SMALL["traffic"]})
    assert res["correct"], res["checks"]


def test_txs_not_settled_on_the_l1_are_not_correct(registry):
    """An L1 whose blocks hold fewer commits than the rollup seals: the
    program still calls the receipts finalized, but their windows' L1
    txs never land in a block."""
    res = core.run_cell("ledger-mixed-uniform", 8, 0.3, False,
                        time.perf_counter(), require_tpu=False,
                        registry=registry,
                        overrides={"config": {"accounts": 1 << 12,
                                              "node": _node(
                                                  registry,
                                                  block_gas_limit=600_000)},
                                   "traffic": SMALL["traffic"]})
    assert not res["correct"]
    assert res["failed"] > 0 and "receipts_wrong" in _wrong(res)
