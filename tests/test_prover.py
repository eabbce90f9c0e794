"""Prover pipeline tests (core/prover.py + the proof-lifecycle API).

Pins the PR-5 contracts:
  * width-1 aggregation is BIT-EQUIVALENT to the pre-pipeline settlement
    path on all three rollup backends — same gas rows (the pre-PR
    per-session amortization reimplemented here as a reference), same
    state root, finalized receipts whose shares still sum to the ledger
    total;
  * aggregation width W amortizes ONE L1 verify across W sessions (the
    paper's gas lever) without touching the committed state;
  * ``client.events()`` yields the same typed sequence for a 1-shard
    ``ShardedRollup`` and a plain ``VectorRollup`` under the same spec
    and workload (modulo shard tags / fabric root fields);
  * identical specs model identical prove/settle timing on the object
    and vector faces (one ``session_latency`` formula);
  * windowed finalization drains proof jobs on the shared window clock
    (receipts walk pending -> sealed -> proved -> finalized);
  * the recursive aggregation fold is the same xor-mix at every level
    (jnp kernel helper == NumPy chunk-fold mirror).
"""
import dataclasses

import numpy as np
import pytest

from repro.api import (ChainSpec, NodeClient, NodeSpec, ProverSpec,
                       RollupSpec, ShardSpec)
from repro.core.gas import DEFAULT_GAS
from repro.kernels.digest import chunk_fold_digests

BACKENDS = [
    NodeSpec(),                                         # VectorRollup
    NodeSpec(chain=ChainSpec(backend="object")),        # object Rollup
    NodeSpec(shards=ShardSpec(count=1, fabric=True)),   # 1-shard fabric
]
BACKEND_IDS = ["vector", "object", "fabric-1"]


def _drive_sessions(spec, n_txs=90, chunk=30, senders=6):
    """Submit ``n_txs`` in ``chunk``-sized settle sessions (seal + close
    per chunk — the window cadence; sessions feed the aggregation stage)
    and force the final flush."""
    client = NodeClient.from_spec(spec)
    receipts = []
    for i in range(n_txs):
        receipts.append(client.submit("submitLocalModel",
                                      f"t{i % senders}"))
        if (i + 1) % chunk == 0:
            client.seal()
            client.target.settle_session()
    client.flush()
    client.run_until(10.0)
    return client, [client.refresh(r) for r in receipts]


def _prepr_reference(session_sizes, gas=DEFAULT_GAS):
    """The pre-pipeline settlement, reimplemented: ONE amortized verify +
    execute per session (old Rollup._settle_session semantics).  Returns
    the expected per-batch (verify, execute) shares, row order."""
    shares = []
    for batches in session_sizes:           # list of per-batch n_txs
        nb = len(batches)
        single = nb == 1 and batches[0] <= 5
        verify = gas.verify_single if single else gas.verify_multi
        execute = gas.execute_single if single else gas.execute_multi
        shares.extend((verify / nb, execute / nb) for _ in batches)
    return shares


@pytest.mark.parametrize("spec", BACKENDS, ids=BACKEND_IDS)
def test_width1_is_bit_equivalent_to_the_prepr_settlement_path(spec):
    """Acceptance pin: default ProverSpec (width 1, eager) reproduces the
    pre-pipeline per-session settlement exactly."""
    client, receipts = _drive_sessions(spec)
    rows = client.target.gas_log
    # session structure: 3 chunks of 30 at batch_size 20 -> [20, 10] x 3
    assert [r["n_txs"] for r in rows] == [20, 10] * 3
    expected = _prepr_reference([[20, 10]] * 3)
    got = [(r["verify"], r["execute"]) for r in rows]
    assert got == expected
    for r in rows:
        assert r["total"] == r["commit"] + r["verify"] + r["execute"]
    # one verify + execute posted per session, timestamped at the
    # session's last seal (the pre-PR posting point)
    aggs = [e for e in client.events() if e.kind == "aggregate_verified"]
    assert len(aggs) == 3
    assert all(a.n_sessions == 1 for a in aggs)
    # receipts walked the full lifecycle and the shares conserve gas
    assert all(r.status == "finalized" for r in receipts)
    total = sum(r["total"] for r in rows)
    assert np.isclose(sum(r.gas_breakdown["amortized"] for r in receipts),
                      total)
    assert np.isclose(sum(r.gas_breakdown["verify_share"]
                          for r in receipts),
                      3 * DEFAULT_GAS.verify_multi)
    assert client.state_root()


def test_same_spec_same_state_root_and_commits_on_every_backend():
    """The settlement redesign must not move the committed state or the
    commit gas: all three backends agree, width makes no difference."""
    roots, commits = set(), set()
    for spec in BACKENDS + [NodeSpec(prover=ProverSpec(agg_width=3))]:
        client, _ = _drive_sessions(spec)
        roots.add(client.state_root())
        commits.add(sum(r["commit"] for r in client.target.gas_log))
    assert len(roots) == 1 and len(commits) == 1


@pytest.mark.parametrize("spec", BACKENDS, ids=BACKEND_IDS)
def test_aggregation_width_amortizes_the_l1_verify(spec):
    """The gas lever: width W folds W sessions into ONE posted verify."""
    spec_w = dataclasses.replace(spec, prover=ProverSpec(agg_width=3))
    base, _ = _drive_sessions(spec)
    wide, receipts = _drive_sessions(spec_w)
    v_base = sum(r["verify"] for r in base.target.gas_log)
    v_wide = sum(r["verify"] for r in wide.target.gas_log)
    assert np.isclose(v_base, 3 * DEFAULT_GAS.verify_multi)
    assert np.isclose(v_wide, DEFAULT_GAS.verify_multi)
    aggs = [e for e in wide.events() if e.kind == "aggregate_verified"]
    assert len(aggs) == 1 and aggs[0].n_sessions == 3
    assert base.state_root() == wide.state_root()
    assert all(r.status == "finalized" for r in receipts)
    # recursive digest: the aggregate folds the session digests with the
    # same xor-mix the batch digests were built with
    prover = getattr(wide.target, "prover")
    sess = [s for a in prover.aggregates for s in a.sessions]
    assert len(sess) == 3
    agg = prover.aggregates[0]
    assert agg.n_txs == 90 and len(agg.batches) == 6


def test_flush_forces_the_partial_aggregate_through():
    spec = NodeSpec(prover=ProverSpec(agg_width=4))
    client, receipts = _drive_sessions(spec)          # only 3 sessions
    assert all(r.status == "finalized" for r in receipts)
    aggs = [e for e in client.events() if e.kind == "aggregate_verified"]
    assert len(aggs) == 1 and aggs[0].n_sessions == 3


def test_single_run_until_confirms_window_finalized_settlements():
    """Regression: run_until must pump the prover BEFORE producing
    blocks — posting the aggregate's verify/execute after the blocks
    that should pack them left the settlement unconfirmed forever."""
    spec = NodeSpec(prover=ProverSpec(agg_width=1, finalize="window",
                                      prove_time=2.0))
    client = NodeClient.from_spec(spec)
    receipts = [client.submit("submitLocalModel", f"t{i}")
                for i in range(20)]
    client.seal()
    client.target.settle_session()
    client.run_until(30.0)                  # ONE call: drain + pack
    assert all(client.refresh(r).status == "finalized" for r in receipts)
    assert client.chain.n_confirmed == client.chain.n_submitted


def test_forced_drain_never_posts_future_settlements():
    """A flush before the modeled proofs drain must post at the session
    close time, not the future drain time — a future-stamped settle tx
    at the L1 mempool head would stall every later submission (FIFO
    head-of-line rule)."""
    spec = NodeSpec(prover=ProverSpec(agg_width=2, finalize="window",
                                      prove_time=50.0))
    client = NodeClient.from_spec(spec)
    receipts = [client.submit("submitLocalModel", f"t{i}")
                for i in range(20)]
    client.flush()              # proofs would drain at ~50s; force now
    aggs = [e for e in client.events() if e.kind == "aggregate_verified"]
    assert len(aggs) == 1 and aggs[0].time <= 1.0
    assert all(client.refresh(r).status == "finalized" for r in receipts)
    client.run_until(5.0)       # nothing stalls behind the settlement
    assert client.chain.n_confirmed == client.chain.n_submitted


# -- typed event stream: fabric == vector (acceptance) -------------------------
def _normalize(ev):
    strip = {"shard": None}
    if ev.kind == "window_settled":
        strip.update(fabric_root="", shard_roots=())
    return dataclasses.replace(ev, **strip)


def test_one_shard_fabric_yields_the_same_event_sequence_as_vector():
    """Acceptance pin: client.events() is uniform across backends — a
    1-shard ShardedRollup and a plain VectorRollup emit the SAME typed
    sequence under the same spec and workload, modulo the shard tags
    (and the fabric-root decoration on WindowSettled)."""
    def drive(spec):
        client, _ = _drive_sessions(spec)
        return client.events()

    plain = drive(NodeSpec())
    fabric = drive(NodeSpec(shards=ShardSpec(count=1, fabric=True)))
    assert len(plain) == len(fabric)
    for a, b in zip(plain, fabric):
        assert _normalize(a) == _normalize(b), (a, b)
    # the fabric's shard tags are the only decoration
    assert {e.shard for e in fabric if e.kind == "batch_sealed"} == {0}
    assert {e.shard for e in plain if e.kind == "batch_sealed"} == {None}


# -- modeled prover latency ----------------------------------------------------
def test_latency_parity_object_vs_vector_and_prepr_formula():
    """Satellite pin: identical specs model identical prove/settle
    timing on both faces — one session_latency formula — and the
    default capacity-1 model equals the pre-pipeline ``nb * prove_time +
    n * per_tx_time``."""
    from repro.api import build_ledger
    ru_spec = RollupSpec(batch_size=20, prove_time=0.9, per_tx_time=0.14)
    obj = build_ledger(NodeSpec(chain=ChainSpec(backend="object"),
                                rollup=ru_spec))
    vec = build_ledger(NodeSpec(rollup=ru_spec))
    for n in (1, 5, 20, 99, 1000):
        nb = max(1, -(-n // 20))
        prepr = nb * 0.9 + n * 0.14
        assert obj.latency(n) == vec.latency(n) == pytest.approx(prepr)
    # more modeled prover workers -> faster drain, never slower
    fast = build_ledger(NodeSpec(rollup=ru_spec,
                                 prover=ProverSpec(capacity=4)))
    assert fast.latency(1000) < vec.latency(1000)
    assert fast.latency(1) == vec.latency(1)


# -- windowed finalization on the shared clock ---------------------------------
def test_windowed_finalization_walks_the_full_receipt_lifecycle():
    spec = NodeSpec(prover=ProverSpec(agg_width=2, finalize="window",
                                      prove_time=5.0))
    client = NodeClient.from_spec(spec)
    receipts = [client.submit("submitLocalModel", f"t{i}")
                for i in range(20)]
    r = receipts[0]
    assert client.refresh(r).status == "pending"
    client.seal()
    client.target.settle_session()            # session 1 closed
    assert client.refresh(r).status == "sealed"   # proof still in flight
    client.run_until(2.0)                     # before the modeled drain
    assert client.refresh(r).status == "sealed"
    client.run_until(30.0)                    # proof drained on the clock
    assert client.refresh(r).status == "proved"
    evs = client.events()
    assert [e.kind for e in evs].count("proof_generated") == 1
    assert not any(e.kind == "aggregate_verified" for e in evs)
    # second session completes the width-2 aggregate at the next pump
    for i in range(20):
        client.submit("submitLocalModel", f"u{i}", at=30.0 + i)
    client.seal()
    client.target.settle_session()
    client.run_until(80.0)
    assert client.refresh(r).status == "finalized"
    aggs = [e for e in client.events() if e.kind == "aggregate_verified"]
    assert len(aggs) == 1 and aggs[0].n_sessions == 2
    # the posting time models the proof drain, not the seal
    assert aggs[0].time >= 35.0


# -- recursive digest fold -----------------------------------------------------
def test_aggregate_digest_fold_matches_the_numpy_mirror():
    from repro.kernels.rollup_digest import rollup_aggregate_digests
    rng = np.random.default_rng(7)
    digests = rng.integers(0, 2**32, 37, dtype=np.uint32)
    for width in (1, 2, 8, 37, 64):
        dev = np.asarray(rollup_aggregate_digests(digests, width))
        mirror = chunk_fold_digests(digests, chunk=width)
        np.testing.assert_array_equal(dev, mirror)
    # and the pipeline's aggregate digest IS that construction applied
    # recursively: batch digests -> session proofs -> aggregate proof
    client, _ = _drive_sessions(NodeSpec(prover=ProverSpec(agg_width=3)))
    prover = client.target.prover
    evs = client.events()
    proofs = {e.batch: e.digest for e in evs
              if e.kind == "proof_generated"}
    assert len(proofs) == 6     # every batch proof drained exactly once
    session_digests = [
        int(chunk_fold_digests(
            np.array([proofs[2 * k], proofs[2 * k + 1]], np.uint32),
            chunk=2)[0])
        for k in range(3)]      # sessions were [batch 2k, batch 2k+1]
    expected = int(chunk_fold_digests(
        np.array(session_digests, np.uint32), chunk=3)[0])
    assert prover.aggregates[0].digest == expected
