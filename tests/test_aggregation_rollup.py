"""Aggregation (Eq. 1) + rollup engine: equivalence and integrity tests."""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # degrade: property tests skip, the rest still run
    from conftest import given, settings, st  # noqa: F401

from repro.core.aggregation import (weighted_average_flat,
                                    weighted_average_tree,
                                    weighted_average_tree_mega)
from repro.core.gas import ROLLUP_BATCH, l1_gas, l2_gas
from repro.core.ledger import Chain, Tx
from repro.core.rollup import BatchProof, Rollup, state_digest
from repro.kernels import ref


# -- Eq. 1 -----------------------------------------------------------------------
@settings(max_examples=50, deadline=None)
@given(st.integers(1, 8), st.integers(1, 64))
def test_weighted_average_properties(n, p):
    rng = np.random.default_rng(n * 100 + p)
    w = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    s = jnp.asarray(rng.uniform(0.01, 1.0, n), jnp.float32)
    out = weighted_average_flat(w, s)
    # convexity: within [min, max] per coordinate
    assert np.all(np.asarray(out) <= np.asarray(jnp.max(w, 0)) + 1e-5)
    assert np.all(np.asarray(out) >= np.asarray(jnp.min(w, 0)) - 1e-5)
    # scale invariance of scores
    out2 = weighted_average_flat(w, s * 7.3)
    np.testing.assert_allclose(out, out2, rtol=1e-5, atol=1e-6)


def test_weighted_average_equal_scores_is_fedavg():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(4, 33)), jnp.float32)
    out = weighted_average_flat(w, jnp.ones(4))
    np.testing.assert_allclose(out, jnp.mean(w, 0), rtol=1e-6)


def test_weighted_average_tree_matches_flat():
    rng = np.random.default_rng(1)
    tree = {"a": jnp.asarray(rng.normal(size=(3, 4, 5)), jnp.float32),
            "b": {"c": jnp.asarray(rng.normal(size=(3, 7)), jnp.float32)}}
    s = jnp.array([0.2, 0.5, 0.9])
    out = weighted_average_tree(tree, s)
    want_a = weighted_average_flat(tree["a"].reshape(3, -1), s).reshape(4, 5)
    np.testing.assert_allclose(out["a"], want_a, rtol=1e-6)


def _close(got, want, dt):
    tol = dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("n,P,dt", [
    (2, 256, jnp.float32),
    (4, 1000, jnp.float32),
    (16, 8192, jnp.bfloat16),
    (64, 4096, jnp.bfloat16),
    (3, 130, jnp.float32),
])
def test_xla_merge_matches_ref(n, P, dt):
    """The Eq. 1 merge the FL cell runs against ``kernels/ref.py``: the
    flat form, and the megastep form over two tasks, the first holding
    zero rows with zero weights past its submitters."""
    rng = np.random.default_rng(n * 10_000 + P)
    k = n + 3
    w = jnp.asarray(rng.normal(size=(2, k, P)), dt).at[0, n:].set(0)
    s = jnp.asarray(rng.uniform(0.05, 1.0, (2, k)),
                    jnp.float32).at[0, n:].set(0.0)
    want = ref.weighted_agg_ref(w[0, :n], s[0, :n])
    _close(weighted_average_flat(w[0, :n], s[0, :n]), want, dt)
    out = weighted_average_tree_mega({"w": w}, s)["w"]
    _close(out[0], want, dt)
    _close(out[1], ref.weighted_agg_ref(w[1], s[1]), dt)


def test_xla_merge_zero_score_trainer_excluded():
    """A trainer scored 0 has no weight in the merge (flat and megastep
    forms), as in the oracle."""
    w = jnp.stack([jnp.ones(256), 100.0 * jnp.ones(256)])
    s = jnp.array([1.0, 0.0])
    np.testing.assert_allclose(weighted_average_flat(w, s), jnp.ones(256),
                               rtol=1e-6)
    np.testing.assert_allclose(ref.weighted_agg_ref(w, s), jnp.ones(256),
                               rtol=1e-6)
    out = weighted_average_tree_mega({"w": w[None]}, s[None])
    np.testing.assert_allclose(out["w"][0], jnp.ones(256), rtol=1e-6)


# -- rollup engine ------------------------------------------------------------------
def _mk_rollup(batch=ROLLUP_BATCH):
    chain = Chain()
    ru = Rollup(chain, batch_size=batch)
    return chain, ru


def test_rollup_state_equals_sequential_l1():
    """Replaying the same txs through L1 directly and through the rollup
    must produce the same final contract state (zk-rollup soundness)."""
    def handler(state, tx):
        state.setdefault("count", 0)
        state["count"] += 1
        state.setdefault("by_sender", {})
        state["by_sender"][tx.sender] = \
            state["by_sender"].get(tx.sender, 0) + tx.payload.get("v", 1)

    chain1 = Chain()
    chain1.register("f", handler)
    chain2, ru = _mk_rollup(batch=8)
    ru.register("f", handler)
    txs = [Tx("f", f"s{i % 3}", {"v": i}, 1000, i * 0.01) for i in range(30)]
    for t in txs:
        chain1.submit(t)
        ru.submit(t)
    chain1.run_until(10.0)
    ru.flush()
    assert state_digest(chain1.state) == state_digest(ru.state)


def test_batch_proof_verifies_and_rejects_tamper():
    chain, ru = _mk_rollup(batch=4)
    def handler(state, tx):
        state["x"] = state.get("x", 0) + 1
    ru.register("f", handler)
    pre = dict(ru.state)
    for i in range(4):
        ru.submit(Tx("f", "s", {}, 10, i * 0.1))
    proof = ru.batches[-1]
    def replay(s):
        for _ in range(4):
            handler(s, None)
        return s
    assert proof.verify(dict(pre), replay)
    bad = BatchProof(proof.batch_id, proof.n_txs, proof.pre_root,
                     "deadbeef" * 4, proof.tx_root)
    assert not bad.verify(dict(pre), replay)


def test_rollup_gas_reduction_headline():
    """Live engine reproduces the paper's 'up to 20x' at 100 publishTask."""
    chain, ru = _mk_rollup()
    for i in range(100):
        ru.submit(Tx("publishTask", f"p{i}", {}, 0, i * 0.01))
    ru.flush()
    live_l2 = sum(b["total"] for b in ru.gas_log)
    assert l1_gas("publishTask", 100) / live_l2 > 20


def test_rollup_batch_boundaries():
    chain, ru = _mk_rollup(batch=20)
    for i in range(50):
        ru.submit(Tx("submitLocalModel", "s", {}, 0, i * 0.01))
    ru.flush()
    assert [b.n_txs for b in ru.batches] == [20, 20, 10]
    assert l2_gas("submitLocalModel", 50)["batches"] == 3
