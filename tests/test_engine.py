"""Vectorized engine tests: object/vector equivalence, multi-lane rollup,
settlement amortization invariants, Table-I regression pins, digests."""
import numpy as np
import pytest

from repro.core.engine import TxArrays, VectorChain, VectorRollup
from repro.core.gas import DEFAULT_GAS, FUNCTIONS, ROLLUP_BATCH, l1_gas
from repro.core.ledger import Chain, Tx, simulate_load
from repro.core.rollup import Rollup
from repro.core.tasks import TaskContract
from repro.core.workloads import make_workload, mixed_function_workload
from repro.kernels.digest import xor_fold_digest


def _random_workload(rng, n):
    """Random mixed-fn workload in sorted submit order (the documented FIFO
    contract; see test_head_of_line_stall for the out-of-order case)."""
    fns = list(FUNCTIONS)
    times = np.sort(rng.uniform(0.0, 10.0, n))
    return [Tx(fns[int(rng.integers(len(fns)))], f"c{int(rng.integers(8))}",
               {}, int(DEFAULT_GAS.l1_per_call[fns[0]]
                       if rng.uniform() < 0.1
                       else rng.integers(20_000, 200_000)), float(t))
            for t in times]


def _run_object(txs, block_gas_limit, block_time, t_end):
    ch = Chain(block_gas_limit=block_gas_limit, block_time=block_time)
    for t in txs:
        ch.submit(t)
    ch.run_until(t_end)
    return ch


def _run_vector(txs, block_gas_limit, block_time, t_end):
    vc = VectorChain(block_gas_limit=block_gas_limit, block_time=block_time)
    vc.submit_arrays(TxArrays.from_txs(txs, vc.fns))
    vc.run_until(t_end)
    return vc


# -- property: vector == object on random workloads ----------------------------
@pytest.mark.parametrize("seed", range(8))
def test_chain_equivalence_random_workloads(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(50, 800))
    limit = int(rng.integers(500_000, 9_000_000))
    bt = float(rng.uniform(0.3, 2.0))
    txs = _random_workload(rng, n)
    oc = _run_object(txs, limit, bt, 12.0)
    vc = _run_vector(txs, limit, bt, 12.0)
    assert len(oc.blocks) == len(vc.blocks)
    for ob, vb in zip(oc.blocks, vc.blocks):
        assert (ob.height, ob.time) == (vb.height, vb.time)
        assert len(getattr(ob, "txs", [])) == getattr(vb, "n_txs", 0) \
            or ob.height == 0
        assert ob.gas_used == vb.gas_used
    assert oc.total_gas == vc.total_gas
    obj_conf = [t.confirm_time for b in oc.blocks for t in b.txs]
    np.testing.assert_array_equal(np.asarray(obj_conf), vc.confirm_times())


def test_simulate_load_engines_identical():
    for fn in FUNCTIONS:
        for rate in (40, 320):
            a = simulate_load(fn, rate, duration=8.0, engine="object")
            b = simulate_load(fn, rate, duration=8.0, engine="vector")
            assert set(a) == set(b)
            for k in a:
                assert np.isclose(a[k], b[k]), (fn, rate, k)


def test_head_of_line_stall_identical():
    """Documented FIFO semantics: a future-timestamped tx submitted out of
    order stalls everything behind it — identically in both engines."""
    txs = [Tx("submitLocalModel", "a", {}, 50_000, 0.5),
           Tx("submitLocalModel", "b", {}, 50_000, 99.0),   # future head
           Tx("submitLocalModel", "c", {}, 50_000, 1.0)]
    oc = _run_object(txs, 9_000_000, 1.0, 5.0)
    vc = _run_vector(txs, 9_000_000, 1.0, 5.0)
    assert sum(len(b.txs) for b in oc.blocks) == 1     # only tx "a"
    assert vc.n_confirmed == 1
    assert oc.total_gas == vc.total_gas == 50_000


def test_oversized_tx_blocks_queue_identically():
    txs = [Tx("submitLocalModel", "a", {}, 10_000_000, 0.1),  # > block limit
           Tx("submitLocalModel", "b", {}, 1_000, 0.2)]
    oc = _run_object(txs, 9_000_000, 1.0, 5.0)
    vc = _run_vector(txs, 9_000_000, 1.0, 5.0)
    assert sum(len(b.txs) for b in oc.blocks) == 0 == vc.n_confirmed


def test_batch_handlers_match_per_tx_handlers():
    rng = np.random.default_rng(7)
    wl = mixed_function_workload(150.0, duration=6.0, seed=11)
    oc = Chain()
    counts = {}
    for fn in FUNCTIONS:
        oc.register(fn, lambda s, tx, fn=fn: counts.__setitem__(
            fn, counts.get(fn, 0) + 1))
    for t in wl.to_txs():
        oc.submit(t)
    oc.run_until(6.0)
    vc = VectorChain(fns=wl.txs.fns)
    TaskContract.register_batch_handlers(vc)
    vc.submit_arrays(wl.txs)
    vc.run_until(6.0)
    assert vc.state.get("calls", {}) == {k: v for k, v in counts.items() if v}
    del rng


def test_interleaved_submit_produce_matches_object():
    """Incremental consolidation: streaming submits between blocks must
    match the object chain (and the one-shot vector submission)."""
    rng = np.random.default_rng(21)
    txs = _random_workload(rng, 400)
    oc = Chain(block_gas_limit=2_000_000)
    vc = VectorChain(block_gas_limit=2_000_000)
    i, t = 0, 0.0
    while t < 12.0:
        while i < len(txs) and txs[i].submit_time <= t + 1.0:
            oc.submit(txs[i])
            vc.submit(txs[i])
            i += 1
        t += 1.0
        oc.produce_block(t)
        vc.produce_block(t)
    assert oc.total_gas == vc.total_gas
    obj_conf = [x.confirm_time for b in oc.blocks for x in b.txs]
    np.testing.assert_array_equal(np.asarray(obj_conf), vc.confirm_times())


def test_submit_shim_preserves_sender_identity():
    """Regression: the object-Tx shim collapsed every sender to id 0."""
    vc = VectorChain()
    TaskContract.register_batch_handlers(vc)
    for sender, n in (("t3", 2), ("t7", 3)):
        for j in range(n):
            vc.submit(Tx("submitLocalModel", sender, {"j": j}, 1000,
                         0.1 * (j + 1)))
    vc.run_until(2.0)
    per = vc.state["calls_by_sender"]["submitLocalModel"]
    assert sorted(per.values()) == [2, 3]
    assert len(per) == 2
    assert vc.sender_id("t3") != vc.sender_id("t7")


def test_vector_rollup_shares_fresh_chain_registry():
    """Regression: `or FnRegistry()` dropped an empty-but-present registry
    (FnRegistry defines __len__, so a fresh one is falsy)."""
    vc = VectorChain()
    assert VectorRollup(vc).fns is vc.fns


def test_reentrant_flush_single_settlement():
    """Regression: a handler calling flush() mid-seal split the session,
    posting verify/execute twice."""
    ch = Chain()
    ru = Rollup(ch, batch_size=4)

    def handler(state, tx):
        ru.flush()                       # must be a no-op mid-seal
    ru.register("f", handler)
    for i in range(6):
        ru.submit(Tx("f", "s", {"i": i}, 0, float(i)))
    ru.flush()
    posted = [t.fn for t in list(ch.mempool)]
    assert posted.count("rollup_verify") == 1
    assert posted.count("rollup_execute") == 1
    rows = ru.gas_log
    assert np.isclose(sum(r["verify"] for r in rows),
                      DEFAULT_GAS.verify_multi)


# -- rollup equivalence + multi-lane -------------------------------------------
@pytest.mark.parametrize("fn,n_calls,batch", [
    ("publishTask", 100, ROLLUP_BATCH), ("submitLocalModel", 50, 20),
    ("calculateSubjectiveRep", 7, 4), ("calculateObjectiveRep", 3, 8)])
def test_rollup_gas_log_equivalence(fn, n_calls, batch):
    oc, vc = Chain(), VectorChain()
    oru = Rollup(oc, batch_size=batch)
    vru = VectorRollup(vc, batch_size=batch, n_lanes=1)
    for i in range(n_calls):
        tx = Tx(fn, f"c{i}", {}, 0, i * 0.01)
        oru.submit(tx)
        vru.submit(tx)
    oru.flush()
    vru.flush()
    assert len(oru.gas_log) == len(vru.gas_log)
    for a, b in zip(oru.gas_log, vru.gas_log):
        for k in ("n_txs", "commit", "verify", "execute", "total"):
            assert np.isclose(a[k], b[k]), (k, a, b)
    oc.run_until(n_calls * 0.01 + 2.0)
    vc.run_until(n_calls * 0.01 + 2.0)
    assert oc.total_gas == vc.total_gas


@pytest.mark.parametrize("lanes", [2, 4])
def test_multi_lane_settlement_invariants(lanes):
    vc = VectorChain()
    vru = VectorRollup(vc, batch_size=10, n_lanes=lanes)
    wl = make_workload("poisson", 60.0, duration=5.0, seed=3)
    vru.submit_arrays(wl.txs)
    vru.flush()
    rows = vru.gas_log
    assert sorted(set(r["lane"] for r in rows)) == list(range(lanes))
    # every submitted tx landed in exactly one batch
    assert sum(r["n_txs"] for r in rows) == len(wl)
    assert all(r["n_txs"] <= 10 for r in rows)
    # amortization invariant: per-row shares sum back to one verify+execute
    verify = DEFAULT_GAS.verify_multi
    execute = DEFAULT_GAS.execute_multi
    assert np.isclose(sum(r["verify"] for r in rows), verify)
    assert np.isclose(sum(r["execute"] for r in rows), execute)
    assert np.isclose(sum(r["total"] for r in rows),
                      sum(r["commit"] for r in rows) + verify + execute)
    # lanes seal concurrently -> strictly better modeled session latency
    assert vru.latency(100) < VectorRollup(VectorChain()).latency(100)


def test_settlement_amortization_rollup_invariants():
    """Rollup (object path): amortized shares sum to the posted proof gas,
    per session, across re-entrant flushes."""
    ch = Chain()
    ru = Rollup(ch, batch_size=5)
    for sess, n in enumerate((12, 7)):
        start = len(ru.gas_log)
        for i in range(n):
            ru.submit(Tx("submitLocalModel", "s", {}, 0, sess + i * 0.01))
        ru.flush()
        rows = ru.gas_log[start:]
        assert np.isclose(sum(r["verify"] for r in rows),
                          DEFAULT_GAS.verify_multi)
        assert np.isclose(sum(r["execute"] for r in rows),
                          DEFAULT_GAS.execute_multi)
    # verify/execute posted exactly once per session
    posted = [t.fn for t in list(ch.mempool)]
    assert posted.count("rollup_verify") == 2
    assert posted.count("rollup_execute") == 2


def test_settlement_survives_gas_log_truncation():
    """Regression: gas_log[-n:] amortization overwrote a PREVIOUS session's
    settled rows when the current session's rows had been removed; indexed
    tracking must leave settled rows untouched."""
    ch = Chain()
    ru = Rollup(ch, batch_size=5)
    for i in range(10):
        ru.submit(Tx("submitLocalModel", "s", {}, 0, i * 0.01))
    ru.flush()
    settled = [dict(r) for r in ru.gas_log]
    # session 2: one batch committed, then its row is dropped (e.g. a
    # memory-bounding truncation) before settlement
    for i in range(5):
        ru.submit(Tx("submitLocalModel", "s", {}, 0, 1.0 + i * 0.01))
    del ru.gas_log[-1]
    ru.flush()
    assert [dict(r) for r in ru.gas_log] == settled   # no misattribution
    assert ru.prover.n_unsettled(ru) == 0


def test_reentrant_handler_submit_defers_seal():
    """A handler submitting back into the rollup during execution must not
    trigger a nested seal against half-executed state; queued txs drain on
    the same flush."""
    ch = Chain()
    ru = Rollup(ch, batch_size=4)
    executed = []

    def handler(state, tx):
        executed.append(tx.tx_id)
        if tx.payload.get("spawn"):
            for j in range(4):
                ru.submit(Tx("f", "child", {"p": (tx.submit_time, j)}, 0,
                             tx.submit_time + 1 + j))
    ru.register("f", handler)
    for i in range(4):
        ru.submit(Tx("f", "root", {"spawn": True}, 0, float(i)))
    ru.flush()
    assert len(executed) == len(set(executed)) == 4 + 16
    assert sum(b.n_txs for b in ru.batches) == 20
    assert all(b.n_txs <= 4 for b in ru.batches)
    rows = ru.gas_log
    assert np.isclose(sum(r["verify"] for r in rows),
                      DEFAULT_GAS.verify_multi)


# -- Table-I regression pins ---------------------------------------------------
def test_table1_gas_pins_and_20x_ratio():
    """Pin Table-I gas totals (both engines) and the 20X headline ratio."""
    pins = {("publishTask", 100): 742115, ("submitLocalModel", 50): 241568}
    for (fn, n), paper_total in pins.items():
        for make in (lambda: Rollup(Chain()),
                     lambda: VectorRollup(VectorChain())):
            ru = make()
            for i in range(n):
                ru.submit(Tx(fn, f"c{i}", {}, 0, i * 0.01))
            ru.flush()
            live = sum(r["total"] for r in ru.gas_log)
            assert abs(live - paper_total) / paper_total < 0.15, \
                (fn, n, live, paper_total)
    for make in (lambda: Rollup(Chain()),
                 lambda: VectorRollup(VectorChain())):
        ru = make()
        for i in range(100):
            ru.submit(Tx("publishTask", f"p{i}", {}, 0, i * 0.01))
        ru.flush()
        live = sum(r["total"] for r in ru.gas_log)
        assert l1_gas("publishTask", 100) / live > 20.0


# -- digests -------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 100, 5000])
def test_numpy_digest_matches_pallas_kernel(n):
    import jax.numpy as jnp
    from repro.kernels.rollup_digest import rollup_digest
    rng = np.random.default_rng(n)
    words = rng.integers(0, 2**32, n, dtype=np.uint32)
    want = int(rollup_digest(jnp.asarray(words), block_p=2048,
                             interpret=True))
    assert xor_fold_digest(words) == want


def test_rollup_word_digests_deterministic_and_tamper_evident():
    def digests(times):
        ru = Rollup(Chain(), batch_size=8)
        for i, t in enumerate(times):
            ru.submit(Tx("submitLocalModel", f"c{i}", {}, 0, t))
        ru.flush()
        return [b.word_digest for b in ru.batches]
    base = [i * 0.01 for i in range(8)]
    d0, d1 = digests(base), digests(base)
    assert d0 == d1 and d0[0] != 0
    tampered = list(base)
    tampered[3] += 0.5
    assert digests(tampered) != d0
    # vector engine seals the same txs -> same per-batch xor-root family
    vru = VectorRollup(VectorChain(), batch_size=8)
    for i, t in enumerate(base):
        vru.submit(Tx("submitLocalModel", f"c{i}", {}, 0, t))
    vru.flush()
    assert vru.batch_digests and all(isinstance(d, int)
                                     for d in vru.batch_digests)
    assert vru.update_digest != 0
