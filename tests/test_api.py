"""Public node API tests (src/repro/api/).

Pins the PR-4 contracts:
  * ``build_ledger`` maps every spec combination to the right backend and
    rejects invalid combinations;
  * spec-built protocol nodes are EQUIVALENT to the legacy kwarg path on
    every backend (same state root, same gas totals, same outputs);
  * ``TxReceipt`` gas equals the ledger's accounted gas — the per-batch
    breakdown matches ``gas_log`` rows and the amortized per-tx shares
    sum back to the total;
  * receipts on a 1-shard ``ShardedRollup`` match ``VectorRollup``
    receipts bit-for-bit (extends the PR-3 equivalence pins);
  * event subscriptions fire for sealed batches / settled sessions /
    fabric windows;
  * the deprecation shim still accepts the old kwargs (with a warning).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (ChainSpec, DONSpec, FLTaskSpec, NodeClient, NodeSpec,
                       ReputationSpec, RollupSpec, ShardSpec, WorkloadSpec,
                       build_ledger, l1_of, preset)
from repro.core.engine import VectorChain, VectorRollup
from repro.core.ledger import Chain, LedgerBackend, simulate_load
from repro.core.rollup import Rollup
from repro.core.shards import ShardedRollup

GAS_KEYS = ("n_txs", "commit", "verify", "execute", "total")


# -- factory mapping -----------------------------------------------------------
def test_build_ledger_maps_specs_to_backends():
    assert isinstance(build_ledger(NodeSpec(rollup=None)), VectorChain)
    assert isinstance(build_ledger(ChainSpec(backend="object")), Chain)
    assert isinstance(build_ledger(NodeSpec()), VectorRollup)
    obj = build_ledger(NodeSpec(chain=ChainSpec(backend="object")))
    assert isinstance(obj, Rollup)
    fab = build_ledger(NodeSpec(shards=ShardSpec(count=2)))
    assert isinstance(fab, ShardedRollup) and fab.n_shards == 2
    one = build_ledger(NodeSpec(shards=ShardSpec(count=1, fabric=True)))
    assert isinstance(one, ShardedRollup) and one.n_shards == 1
    plain = build_ledger(NodeSpec(shards=ShardSpec(count=1)))
    assert isinstance(plain, VectorRollup)
    # every face satisfies the one LedgerBackend protocol
    for backend in (obj, fab, one, plain):
        assert isinstance(backend, LedgerBackend)
        assert l1_of(backend) is backend.l1


def test_spec_validation_rejects_bad_combinations():
    with pytest.raises(ValueError):
        ChainSpec(backend="quantum")
    with pytest.raises(ValueError):
        ShardSpec(count=0)
    with pytest.raises(ValueError):
        NodeSpec(chain=ChainSpec(backend="object"),
                 shards=ShardSpec(count=2))
    with pytest.raises(ValueError):
        NodeSpec(rollup=None, shards=ShardSpec(count=2))
    # object Rollup has no lanes/digest routing: reject, don't drop
    with pytest.raises(ValueError):
        NodeSpec(chain=ChainSpec(backend="object"),
                 rollup=RollupSpec(n_lanes=8))
    with pytest.raises(KeyError):
        preset("no-such-preset")


def test_rollup_spec_fields_reach_the_backend():
    spec = NodeSpec(chain=ChainSpec(block_time=0.5, block_gas_limit=10**6),
                    rollup=RollupSpec(batch_size=7, n_lanes=3))
    ru = build_ledger(spec)
    assert ru.batch_size == 7 and ru.n_lanes == 3
    assert ru.l1.block_time == 0.5 and ru.l1.block_gas_limit == 10**6


def test_workload_spec_is_make_workload_as_data():
    from repro.core.workloads import make_workload
    ws = WorkloadSpec.make("bursty", 50.0, duration=5.0, seed=3)
    a, b = ws.build(), make_workload("bursty", 50.0, duration=5.0, seed=3)
    np.testing.assert_array_equal(a.txs.submit_time, b.txs.submit_time)
    np.testing.assert_array_equal(a.txs.gas, b.txs.gas)
    assert a.name == b.name


# -- receipts ------------------------------------------------------------------
def _drive(spec, n=50):
    client = NodeClient.from_spec(spec)
    receipts = [client.submit("submitLocalModel", f"t{i % 8}")
                for i in range(n)]
    client.flush()
    client.run_until(10.0)
    return client, [client.refresh(r) for r in receipts]


@pytest.mark.parametrize("spec", [
    NodeSpec(),                                         # VectorRollup
    NodeSpec(chain=ChainSpec(backend="object")),        # object Rollup
    NodeSpec(shards=ShardSpec(count=2)),                # fabric
], ids=["vector-rollup", "object-rollup", "fabric-2"])
def test_receipt_gas_equals_ledger_accounted_gas(spec):
    """Satellite pin: receipt gas == the ledger's accounted gas."""
    client, receipts = _drive(spec)
    target = client.target
    assert all(r.status == "finalized" for r in receipts)
    # per-batch breakdown equals the ledger's own gas_log row
    log = target.gas_log
    for r in receipts:
        row = [x for x in log
               if x["batch"] == r.batch
               and (r.shard is None or x.get("shard", r.shard) == r.shard)]
        assert len(row) == 1
        row = row[0]
        assert r.gas_breakdown["batch_commit"] == row["commit"]
        assert r.gas_breakdown["batch_verify"] == row["verify"]
        assert r.gas_breakdown["batch_execute"] == row["execute"]
        assert r.gas_breakdown["batch_total"] == row["total"]
    # amortized per-tx shares sum back to the ledger total (receipts
    # cover every sealed tx exactly once)
    total = sum(row["total"] for row in log)
    assert sum(row["n_txs"] for row in log) == len(receipts)
    assert np.isclose(sum(r.gas_breakdown["amortized"] for r in receipts),
                      total)
    # the commit landed in a real L1 block
    assert all(r.block is not None and r.block_hash for r in receipts)


def test_single_shard_fabric_receipts_match_vector_rollup_bit_for_bit():
    """Satellite pin: receipts on ShardedRollup(count=1) == VectorRollup
    receipts, field for field (the fabric only adds the shard tag)."""
    _, plain = _drive(NodeSpec(), n=64)
    _, fab = _drive(NodeSpec(shards=ShardSpec(count=1, fabric=True)), n=64)
    assert len(plain) == len(fab)
    for a, b in zip(plain, fab):
        assert b.shard == 0
        assert a == dataclasses.replace(b, shard=None)


def test_chain_only_receipts_confirm_and_account_all_gas():
    spec = NodeSpec(rollup=None)
    client = NodeClient.from_spec(spec)
    receipts = [client.submit("publishTask", f"p{i}") for i in range(20)]
    assert all(r.status == "pending" for r in receipts)
    client.run_until(5.0)
    for r in receipts:
        client.refresh(r)
    assert all(r.status == "confirmed" for r in receipts)
    chain = client.chain
    assert sum(r.gas_breakdown["intrinsic"] for r in receipts) == \
        chain.total_gas
    for r in receipts:
        assert r.block_hash == chain.blocks[r.block].block_hash
        assert r.confirm_time is not None


def test_submit_arrays_receipts_cover_a_workload():
    wl = WorkloadSpec.make("poisson", 40.0, duration=4.0, seed=1).build()
    client = NodeClient.from_spec(NodeSpec(shards=ShardSpec(count=4)))
    receipts = client.submit_arrays(wl.txs)
    assert len(receipts) == len(wl)
    client.flush()
    client.run_until(10.0)
    for r in receipts:
        client.refresh(r)
    assert all(r.status == "finalized" for r in receipts)
    # conservation across shards: every tx in exactly one sealed batch
    total = sum(row["total"] for row in client.target.gas_log)
    assert np.isclose(sum(r.gas_breakdown["amortized"] for r in receipts),
                      total)
    assert {r.shard for r in receipts} <= {0, 1, 2, 3}


# -- events --------------------------------------------------------------------
def test_typed_event_stream_covers_the_proof_lifecycle():
    client = NodeClient.from_spec(NodeSpec(shards=ShardSpec(count=2)))
    for i in range(30):
        client.submit("submitLocalModel", f"t{i}")
    client.flush()
    client.run_until(5.0)
    evs = client.events()
    kinds = [e.kind for e in evs]
    for kind in ("batch_sealed", "proof_generated", "aggregate_verified",
                 "window_settled", "block_packed"):
        assert kind in kinds, kinds
    sealed = [e for e in evs if e.kind == "batch_sealed"]
    assert sum(e.n_txs for e in sealed) == 30
    assert all(e.shard in (0, 1) for e in sealed)
    windows = [e for e in evs if e.kind == "window_settled"]
    assert windows[-1].fabric_root and len(windows[-1].shard_roots) == 2
    # the stream is a drain: a second call yields only what's new
    assert client.events() == []
    client.flush()
    assert [e.kind for e in client.events()] == ["window_settled"]
    # events are a total order under one monotonic seq
    assert [e.seq for e in evs] == sorted(e.seq for e in evs)


def test_legacy_subscribe_shim_still_fires_with_a_warning():
    client = NodeClient.from_spec(NodeSpec(shards=ShardSpec(count=2)))
    sealed, settled, windows = [], [], []
    with pytest.warns(DeprecationWarning, match="events"):
        client.subscribe("batch_sealed", sealed.append)
    with pytest.warns(DeprecationWarning):
        client.subscribe("session_settled", settled.append)
    with pytest.warns(DeprecationWarning):
        client.subscribe("window_settled", windows.append)
    for i in range(30):
        client.submit("submitLocalModel", f"t{i}")
    client.flush()
    assert sealed and settled and windows
    assert all("shard" in e for e in sealed + settled)
    assert sum(e["n_txs"] for e in sealed) == 30
    assert "fabric_root" in windows[-1]


def test_chain_only_nodes_emit_block_events_and_report_capabilities():
    """Satellite pin: a chain-only node is a smaller event surface, not
    an error — block_packed flows through events(), capabilities() says
    what the backend supports, and only unsupported callback hooks
    raise."""
    bare = NodeClient.from_spec(NodeSpec(rollup=None))
    # vector chain-only: block production + the fused-loop path marker
    assert bare.capabilities() == frozenset({"block_packed",
                                             "fused_window_loop"})
    full = NodeClient.from_spec(NodeSpec())
    assert "aggregate_verified" in full.capabilities()
    assert "block_packed" in full.capabilities()
    for i in range(10):
        bare.submit("publishTask", f"p{i}")
    bare.run_until(3.0)
    blocks = bare.events(kinds=("block_packed",))
    assert blocks and sum(e.n_txs for e in blocks) == 10
    assert all(e.block_hash for e in blocks)
    # the legacy shim works for the chain's own hook...
    seen = []
    with pytest.warns(DeprecationWarning):
        bare.subscribe("block_packed", seen.append)
    bare.run_until(4.0)
    assert seen
    # ...and still rejects rollup-only hooks (with the capabilities)
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="capabilities"):
            bare.subscribe("batch_sealed", lambda e: None)


def test_object_rollup_events_and_provenance():
    client = NodeClient.from_spec(
        NodeSpec(chain=ChainSpec(backend="object")))
    receipts = [client.submit("calculateObjectiveRep", "t0")
                for _ in range(25)]
    client.flush()
    client.run_until(5.0)
    for r in receipts:
        client.refresh(r)
    sealed = client.events(kinds=("batch_sealed",))
    assert [e.n_txs for e in sealed] == [20, 5]
    assert [r.batch for r in receipts] == [0] * 20 + [1] * 5
    assert all(r.l1_ref for r in receipts)      # commit tx ids
    # proof lifecycle provenance rides on the receipt
    assert all(r.proof_ref is not None and r.aggregate_ref is not None
               for r in receipts)


# -- protocol-node equivalence: spec path == legacy kwarg path -----------------
@pytest.fixture(scope="module")
def tiny_world():
    from repro.data.synthetic import gaussian_clusters
    from repro.models.mlp import TinyMLP
    from repro.optim.optimizers import OptimizerSpec, make_optimizer
    model = TinyMLP(16, 8, 4)
    opt = make_optimizer(OptimizerSpec(name="sgdm", lr=0.1, grad_clip=5.0))
    tr_x, tr_y = gaussian_clusters(256, 16, 4, seed=1, noise=0.5)
    vx, vy = gaussian_clusters(64, 16, 4, seed=2, noise=0.5)
    val = {"x": jnp.asarray(vx), "labels": jnp.asarray(vy)}

    def bf(c, r):
        g = np.random.default_rng((c * 9973 + r) % 2**31)
        idx = g.integers(0, len(tr_x), 8)
        return {"x": jnp.asarray(tr_x[idx]), "labels": jnp.asarray(tr_y[idx])}

    return model, opt, val, bf, model.accuracy_fn()


def _agents(model, opt, store, bf, n=3):
    from repro.fl.client import ClientConfig, TrainingAgent
    from repro.fl.dp import DPConfig
    behaviors = ["good", "good", "malicious"]
    return [TrainingAgent(
        ClientConfig(f"trainer{i}", behaviors[i], local_steps=2,
                     dp=DPConfig(noise_multiplier=0.05)),
        model, opt, store, bf, seed=i) for i in range(n)]


def _run_protocol(world, node):
    model, opt, val, bf, eval_fn = world
    res = node.run_task(FLTaskSpec("t0", rounds=2),
                        _agents(model, opt, node.store, bf), bf)
    if node.rollup is not None:
        node.rollup.flush()
    return res


LEGACY_CONFIGS = [
    ({"engine": "object"}, NodeSpec(chain=ChainSpec(backend="object"))),
    ({"engine": "object", "use_rollup": False},
     NodeSpec(chain=ChainSpec(backend="object"), rollup=None)),
    ({"engine": "vector"}, NodeSpec()),
    ({"engine": "vector", "use_rollup": False}, NodeSpec(rollup=None)),
    ({"engine": "vector", "n_shards": 2},
     NodeSpec(shards=ShardSpec(count=2))),
]


@pytest.mark.parametrize("legacy,spec", LEGACY_CONFIGS,
                         ids=["obj", "obj-l1", "vec", "vec-l1", "fabric"])
def test_spec_node_equivalent_to_legacy_node(tiny_world, legacy, spec):
    """Acceptance pin: NodeSpec/build_ledger construction produces the
    same state root and total gas as the legacy constructor path."""
    from repro.fl.server import AutoDFL
    model, opt, val, bf, eval_fn = tiny_world
    with pytest.warns(DeprecationWarning):
        node_a = AutoDFL(model, opt, 3, eval_fn, val, **legacy)
    res_a = _run_protocol(tiny_world, node_a)
    node_b = AutoDFL(model, opt, 3, eval_fn, val, spec=spec)
    res_b = _run_protocol(tiny_world, node_b)

    assert node_a.chain.total_gas == node_b.chain.total_gas
    assert node_a.protocol_calls == node_b.protocol_calls
    assert node_a._target().state_root() == node_b._target().state_root()
    np.testing.assert_array_equal(res_a.scores, res_b.scores)
    np.testing.assert_array_equal(res_a.reputations, res_b.reputations)
    assert res_a.payouts == res_b.payouts
    if node_a.rollup is not None:
        assert [tuple(r[k] for k in GAS_KEYS)
                for r in node_a.rollup.gas_log] == \
            [tuple(r[k] for k in GAS_KEYS) for r in node_b.rollup.gas_log]


def test_node_client_reads_protocol_account_state(tiny_world):
    from repro.fl.server import AutoDFL
    model, opt, val, bf, eval_fn = tiny_world
    node = AutoDFL(model, opt, 3, eval_fn, val, spec=NodeSpec())
    _run_protocol(tiny_world, node)
    client = node.client()
    acct = client.get_account("trainer0")
    assert acct.account_id == node._target().sender_id("trainer0")
    assert acct.submissions > 0
    np.testing.assert_allclose(acct.reputation,
                               float(np.asarray(node.book.reputation)[0]))
    np.testing.assert_allclose(acct.balance,
                               node.escrow.balances["trainer0"])
    assert client.state_root() == node._target().state_root()
    # unknown addresses are a read, not a mint
    before = dict(node._target()._sender_ids)
    assert client.get_account("nobody").account_id is None
    assert node._target()._sender_ids == before


# -- deprecation shim ----------------------------------------------------------
def test_legacy_kwargs_warn_but_work(tiny_world):
    from repro.fl.server import AutoDFL
    model, opt, val, bf, eval_fn = tiny_world
    with pytest.warns(DeprecationWarning, match="NodeSpec"):
        node = AutoDFL(model, opt, 3, eval_fn, val, engine="vector",
                       n_shards=2, shard_route="least_loaded")
    assert isinstance(node.rollup, ShardedRollup)
    assert node.rollup.route == "least_loaded"
    with pytest.warns(DeprecationWarning, match="ChainSpec"):
        m = simulate_load("publishTask", 10.0, duration=2.0, engine="object")
    assert m["submitted"] == 20
    # spec= and legacy kwargs are mutually exclusive — including the
    # defaulted ones a mixed call would otherwise silently shadow
    with pytest.raises(ValueError):
        AutoDFL(model, opt, 3, eval_fn, val, engine="vector",
                spec=NodeSpec())
    with pytest.raises(ValueError):
        AutoDFL(model, opt, 3, eval_fn, val, seed=1, spec=NodeSpec())
    with pytest.raises(ValueError):              # contradicting trainer count
        AutoDFL(model, opt, 3, eval_fn, val, spec=NodeSpec(n_trainers=8))
    with pytest.raises(ValueError):
        simulate_load("publishTask", 10.0, block_time=0.5, spec=ChainSpec())
    # loose task kwargs conflict with an explicit FLTaskSpec
    node = AutoDFL(model, opt, 3, eval_fn, val, spec=NodeSpec())
    with pytest.raises(ValueError):
        node.run_task(FLTaskSpec("t0", rounds=2), [], rounds=3)
    # payloads are an object-backend feature; SoA engines drop them by
    # design, so the client refuses instead of diverging per backend
    with pytest.raises(ValueError):
        NodeClient.from_spec(NodeSpec()).submit(
            "publishTask", "p0", payload={"reward": 5})


def test_per_instance_reputation_and_don_defaults(tiny_world):
    """Satellite pin: no shared mutable default ReputationParams/DONConfig
    instances across nodes."""
    from repro.fl.server import AutoDFL
    model, opt, val, bf, eval_fn = tiny_world
    a = AutoDFL(model, opt, 2, eval_fn, val, spec=NodeSpec())
    b = AutoDFL(model, opt, 2, eval_fn, val, spec=NodeSpec())
    assert a.rep_params == b.rep_params and a.rep_params is not b.rep_params
    assert a.don == b.don and a.don is not b.don
    # spec-level constants flow through to the node
    c = AutoDFL(model, opt, 2, eval_fn, val, spec=NodeSpec(
        reputation=ReputationSpec(gamma=0.7), don=DONSpec(n_oracles=3)))
    assert c.rep_params.gamma == 0.7 and c.don.n_oracles == 3
    assert len(c.val_slices) == 3
