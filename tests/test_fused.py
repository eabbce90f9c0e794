"""Fused compiled window loop (core/fused.py): equivalence contract.

A fused Scheduler run and a Python-stepped run of the same schedule must
be bit-identical: typed event streams, state roots, gas logs, blocks,
confirm times, rollup provenance and task results.  Pinned here at two
levels:

  * FL end-to-end: full Scheduler runs (multi-task cohorts, background
    traffic, rollup on/off) with ``fused=True`` vs ``fused=False``;
  * ledger property: hypothesis-driven random window schedules (task
    counts, lane counts, batch sizes, prover capacities, seal cadence,
    gas mixes) on the raw VectorChain/VectorRollup pair.

Plus the fused program's shape: one ``lax.scan`` while-loop in the
packing kernel's HLO, cost ~linear in block count (analysis/hlo_cost).
"""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from conftest import given, settings, st  # noqa: F401

from repro.core.engine import FnRegistry, TxArrays, VectorChain, VectorRollup
from repro.core.fused import FusedWindowLoop, supports_fused
from repro.core.workloads import make_workload
from repro.data.synthetic import gaussian_clusters
from repro.fl.cohort import CohortKernels, VectorCohort, batched_batch_fn
from repro.fl.dp import DPConfig
from repro.fl.scheduler import Scheduler
from repro.fl.server import AutoDFL
from repro.models.mlp import TinyMLP
from repro.optim.optimizers import OptimizerSpec, make_optimizer

D_IN, D_H, N_CLS = 32, 16, 10
BEHAVIORS = ["good", "good", "malicious", "lazy"]


@pytest.fixture(scope="module")
def tiny_world():
    model = TinyMLP(D_IN, D_H, N_CLS)
    opt = make_optimizer(OptimizerSpec(name="sgdm", lr=0.1, grad_clip=5.0))
    tr_x, tr_y = gaussian_clusters(1024, D_IN, N_CLS, seed=1, noise=0.5)
    vx, vy = gaussian_clusters(100, D_IN, N_CLS, seed=2, noise=0.5)
    val = {"x": jnp.asarray(vx), "labels": jnp.asarray(vy)}

    def bf(c, r):
        g = np.random.default_rng((c * 9973 + r) % 2**31)
        idx = g.integers(0, len(tr_x), 8)
        return {"x": jnp.asarray(tr_x[idx]), "labels": jnp.asarray(tr_y[idx])}

    kern = CohortKernels(model, opt, DPConfig(noise_multiplier=0.05))
    return model, opt, val, bf, model.accuracy_fn(), kern


def _run_schedule(world, fused, seal_every=2, bg=True, use_rollup=True,
                  n_tasks=3, n_lanes=1):
    model, opt, val, bf, eval_fn, kern = world
    n = len(BEHAVIORS)
    node = AutoDFL(model, opt, n, eval_fn, val, engine="vector",
                   use_rollup=use_rollup, trainer_funds=50.0)
    if use_rollup and n_lanes > 1:
        node.rollup.n_lanes = n_lanes
    background = make_workload("poisson", 20.0, duration=10.0, seed=3,
                               fn="bgPing") if bg else None
    sch = Scheduler(node, seal_every=seal_every, background=background,
                    fused=fused)
    for t in range(n_tasks):
        cohort = VectorCohort(model, opt, batched_batch_fn(bf, 2),
                              node.store, behaviors=BEHAVIORS,
                              local_steps=2,
                              dp=DPConfig(noise_multiplier=0.05), seed=t,
                              kernels=kern)
        sch.add_task(f"task{t}", cohort, rounds=3, start_window=t % 2)
    res = sch.run()
    return node, sch, res


def _assert_ledgers_equal(na, nb):
    """chain+rollup state equality down to provenance and event streams."""
    ea, eb = na.chain.events._events, nb.chain.events._events
    assert len(ea) == len(eb), (len(ea), len(eb))
    for x, y in zip(ea, eb):
        assert x == y, f"\nstepped {x}\nfused   {y}"
    assert na.chain.total_gas == nb.chain.total_gas
    assert na.chain.blocks == nb.chain.blocks
    np.testing.assert_array_equal(na.chain.confirm_times(),
                                  nb.chain.confirm_times())
    ra, rb = na.rollup, nb.rollup
    if ra is None:
        assert rb is None
        return
    assert ra.gas_log == rb.gas_log
    assert ra.batch_digests == rb.batch_digests
    assert ra.update_digest == rb.update_digest
    assert ra.batch_commit_ref == rb.batch_commit_ref
    assert ra.batch_settle_ref == rb.batch_settle_ref
    assert ra._prov_starts == rb._prov_starts
    for x, y in zip(ra._prov_batches, rb._prov_batches):
        np.testing.assert_array_equal(x, y)
    assert (ra.n_batches, ra._next_seq, ra._sealed_seq) == \
        (rb.n_batches, rb._next_seq, rb._sealed_seq)


# -- FL end-to-end: fused Scheduler == stepped Scheduler -----------------------
@pytest.mark.parametrize("cfg", [
    dict(seal_every=2, bg=True),
    dict(seal_every=0, bg=True),                  # seal only at flush
    dict(seal_every=1, bg=False, n_lanes=2, n_tasks=2),
    dict(seal_every=2, bg=True, use_rollup=False),    # chain-only node
], ids=["seal2-bg", "seal0-bg", "lanes2", "no-rollup"])
def test_fused_scheduler_bit_identical(tiny_world, cfg):
    na, sa, ra = _run_schedule(tiny_world, fused=False, **cfg)
    nb, sb, rb = _run_schedule(tiny_world, fused=True, **cfg)
    _assert_ledgers_equal(na, nb)
    assert na.state_arrays.root() == nb.state_arrays.root()
    for t in ra:
        np.testing.assert_array_equal(ra[t].scores, rb[t].scores)
        np.testing.assert_array_equal(ra[t].reputations, rb[t].reputations)
        assert ra[t].payouts == rb[t].payouts
    assert [repr(w) for w in sa.window_records] == \
        [repr(w) for w in sb.window_records]
    assert [repr(s) for s in sa.settlement_records] == \
        [repr(s) for s in sb.settlement_records]


def test_fused_auto_routes_vector_and_falls_back(tiny_world):
    """fused='auto' (the default) engages on VectorChain nodes; explicit
    fused=False never constructs a loop; supports_fused gates on types."""
    model, opt, val, bf, eval_fn, kern = tiny_world
    node = AutoDFL(model, opt, len(BEHAVIORS), eval_fn, val,
                   engine="vector", trainer_funds=50.0)
    assert supports_fused(node.chain, node.rollup)
    obj = AutoDFL(model, opt, len(BEHAVIORS), eval_fn, val,
                  engine="object", trainer_funds=50.0)
    assert not supports_fused(obj.chain, obj.rollup)
    # object engine under the default 'auto' must run the stepped path
    from repro.fl.client import ClientConfig, TrainingAgent
    agents = [TrainingAgent(
        ClientConfig(f"trainer{i}", BEHAVIORS[i], local_steps=2,
                     dp=DPConfig(noise_multiplier=0.05)),
        model, opt, obj.store, bf, seed=i) for i in range(len(BEHAVIORS))]
    sch = Scheduler(obj, seal_every=2)
    sch.add_task("t0", agents, rounds=2)
    res = sch.run()
    assert sch._loop is None and "t0" in res


# -- ledger property: random window schedules ---------------------------------
def _ledger_traffic(rng, n_tasks, n_windows, fns, max_txs):
    for f in ("publishTask", "submitLocalModel", "calculateObjectiveRep",
              "updateReputation"):
        fns.id(f)
    out, t = [], 0.0
    for _w in range(n_windows):
        row = []
        for _m in range(n_tasks):
            k = int(rng.integers(1, max_txs + 1))
            times = t + 0.01 * np.arange(1, k + 1)
            t = float(times[-1])
            row.append(TxArrays(
                times, rng.integers(21_000, 60_000, k).astype(np.int64),
                rng.integers(0, 4, k).astype(np.int32),
                rng.integers(0, 64, k).astype(np.int32), fns))
        out.append(row)
    return out


def _drive(chain, rollup, loop, traffic, seal_every, use_rollup):
    target = rollup if use_rollup else chain
    face = loop if loop is not None else target
    t = 0.0
    for w, row in enumerate(traffic):
        for b in row:
            loop.submit(target, b) if loop is not None \
                else target.submit_arrays(b)
        if use_rollup and seal_every and (w + 1) % seal_every == 0:
            face.seal()
        t_end = max(t + 1.0, float(row[-1].submit_time[-1]))
        if use_rollup:
            face.pump(t_end)
        (loop or chain).run_until(t_end)
        t = t_end
    if use_rollup:
        face.flush()
    (loop or chain).run_until(t + 3.0)
    if loop is not None:
        loop.execute()


class _N:
    """Minimal node shim for _assert_ledgers_equal."""

    def __init__(self, chain, rollup):
        self.chain, self.rollup = chain, rollup


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 8), st.integers(2, 8),
       st.sampled_from([1, 2, 4]), st.sampled_from([0, 1, 2, 3]),
       st.sampled_from([2, 4, 8]), st.booleans())
def test_fused_ledger_property(seed, n_tasks, n_windows, n_lanes,
                               seal_every, batch_size, use_rollup):
    """Random task/lane/prover/seal configs: the fused plan replay leaves
    the ledger bit-identical to the stepped calls it journals."""
    def build():
        chain = VectorChain()
        rollup = None
        if use_rollup:
            rollup = VectorRollup(chain, n_lanes=n_lanes,
                                  batch_size=batch_size, agg_width=4,
                                  prover_capacity=2)
        return chain, rollup

    rng = np.random.default_rng(seed)
    fns = FnRegistry()
    raw = _ledger_traffic(rng, n_tasks, n_windows, fns, max_txs=6)

    ca, ra_ = build()
    _drive(ca, ra_, None, raw, seal_every, use_rollup)
    cb, rb_ = build()
    loop = FusedWindowLoop(cb, rb_)
    _drive(cb, rb_, loop, raw, seal_every, use_rollup)
    _assert_ledgers_equal(_N(ca, ra_), _N(cb, rb_))


def test_fused_loop_single_use():
    chain = VectorChain()
    loop = FusedWindowLoop(chain)
    loop.run_until(1.0)
    loop.execute()
    with pytest.raises(AssertionError):
        loop.execute()


def test_fused_adopts_preexisting_pending():
    """Txs staged on the rollup BEFORE the loop exists are covered by the
    loop's first planned seal, exactly like a stepped seal would."""
    def build():
        chain = VectorChain()
        return chain, VectorRollup(chain, n_lanes=2, agg_width=4)

    fns = FnRegistry()
    early = TxArrays(np.array([0.01, 0.02]), np.array([30_000, 30_000]),
                     np.array([fns.id("publishTask")] * 2, np.int32),
                     np.array([0, 1], np.int32), fns)
    late = TxArrays(np.array([0.5]), np.array([30_000]),
                    np.array([fns.id("publishTask")], np.int32),
                    np.array([2], np.int32), fns)

    ca, ra = build()
    ra.submit_arrays(early)
    ra.submit_arrays(late)
    ra.seal()
    ra.pump(2.0)
    ca.run_until(2.0)
    ra.flush()

    cb, rb = build()
    rb.submit_arrays(early)          # staged pre-loop
    loop = FusedWindowLoop(cb, rb)
    loop.submit(rb, late)
    loop.seal()
    loop.pump(2.0)
    loop.run_until(2.0)
    loop.flush()
    loop.execute()
    _assert_ledgers_equal(_N(ca, ra), _N(cb, rb))


# -- the packer sees only the unconfirmed tail ---------------------------------
def _tail_windows(fns):
    """Chain-only traffic, one ``TxArrays`` (or None) a 1 s window: light
    windows that drain, bursts over the block gas cap, a stuck head-of-
    line tx stamped 2.5 s ahead, and windows with nothing to pack."""
    rng = np.random.default_rng(7)
    fid = fns.id("bgPing")
    out = []
    for w in range(80):
        if w in (5, 6, 30):                      # nothing submitted
            out.append(None)
            continue
        k = 16 if w % 9 == 3 else int(rng.integers(3, 9))
        t = np.sort(rng.uniform(w, w + 1.0, k))
        if w == 12:                              # stuck head of line
            t[0] = w + 3.5
        out.append(TxArrays(t, rng.integers(21_000, 60_000, k)
                            .astype(np.int64),
                            np.full(k, fid, np.int32),
                            rng.integers(0, 64, k).astype(np.int32), fns))
    return out


def test_fused_packs_only_the_unconfirmed_tail(monkeypatch):
    """A fused loop a window over one chain whose history grows past 20x
    a window's rows: every ``block_pack`` call gets the unconfirmed tail
    ``[_ptr, _n)`` with its gas rebased and ``ptr0 == 0``, ``pack.rows``
    counts that tail, and blocks, confirm times, receipts and
    ``BlockPacked`` events equal the stepped ``produce_block`` run."""
    from repro import obs
    from repro.kernels import factory
    fns = FnRegistry()
    windows = _tail_windows(fns)
    limit = 200_000                    # about 5 txs: bursts carry over

    stepped = VectorChain(block_time=0.25, block_gas_limit=limit)
    for w, b in enumerate(windows):
        if b is not None:
            stepped.submit_arrays(b)
        stepped.run_until(w + 1.0)

    chain = VectorChain(block_time=0.25, block_gas_limit=limit)
    real = factory.get_kernel("block_pack")
    tails: list = []

    def spy(tmax, gcum, times, n_vis, gas_limit, ptr0):
        lo, hi = chain._ptr, chain._n
        assert ptr0 == 0 and len(tmax) == len(gcum) == hi - lo
        np.testing.assert_array_equal(tmax, chain._tmax[lo:hi])
        np.testing.assert_array_equal(
            gcum, chain._gcum[lo:hi] - (chain._gcum[lo - 1] if lo else 0))
        assert ((0 <= n_vis) & (n_vis <= hi - lo)).all()
        tails.append((lo, hi))
        return real(tmax, gcum, times, n_vis, gas_limit, ptr0)

    monkeypatch.setitem(factory._REGISTRY["block_pack"],
                        factory.resolve_impl("block_pack"), spy)
    rows, carry, blocks_per = [], [], []
    for w, b in enumerate(windows):
        obs.reset()
        h0 = len(chain.blocks)
        loop = FusedWindowLoop(chain)
        if b is not None:
            loop.submit(chain, b)
        loop.run_until(w + 1.0)
        loop.execute()
        lo, hi = tails[-1]
        assert obs.counters()["pack.rows"] == hi - lo
        rows.append(hi - lo)
        carry.append(chain._n - chain._ptr)
        blocks_per.append(len(chain.blocks) - h0)
    obs.reset()

    assert len(tails) == len(windows)
    biggest = max(len(b) for b in windows if b is not None)
    assert chain._n >= 20 * biggest
    assert tails[0][0] == 0 and tails[0][1] > 0            # ptr0 == 0
    assert 0 in rows                                        # empty tail
    assert min(blocks_per) == max(blocks_per) == 4          # multi-block
    # the tail stays within a window's rows plus the carry over a burst
    # or the stuck head, however long the history grows
    assert max(rows) <= 4 * biggest
    assert max(rows[-10:]) <= 4 * biggest < chain._n // 5
    # the gas cap binds: a block leaves a time-eligible tx behind
    n = stepped._n
    assert any(blk.stop < n and stepped._tmax[blk.stop] <= blk.time
               for blk in stepped.blocks[1:])
    # the stuck head holds its window's rows and the next ones
    assert carry[11] == 0 and carry[12] > 0 and carry[13] > len(windows[13])

    _assert_ledgers_equal(_N(stepped, None), _N(chain, None))
    assert chain._ptr == stepped._ptr == chain._n
    for i in range(chain._n):
        assert chain.block_of(i) == stepped.block_of(i)
        assert chain.confirm_time_of(i) == stepped.confirm_time_of(i)


# -- fused program shape: HLO cost of the packing scan ------------------------
def test_block_pack_scan_hlo_cost():
    from repro.analysis.hlo_cost import analyze
    from repro.kernels.block_pack import fused_scan_lowering
    small = analyze(fused_scan_lowering(1024, 16))
    big = analyze(fused_scan_lowering(1024, 64))
    # one sequential while-loop over blocks, cost ~linear in block count:
    # 4x the blocks => ~4x the flops (same mempool, same search depth)
    assert small.flops > 0
    ratio = big.flops / small.flops
    assert 2.0 <= ratio <= 8.0, ratio
    hlo = fused_scan_lowering(1024, 64)
    assert hlo.count("while(") + hlo.count("while (") >= 1


# -- chip_smoke.py's comparisons, at a tiny size -------------------------------
def test_smoke_ledger_auto_equals_forced_mirror(chip_selection):
    """The smoke's ledger check: the TPU kernel selection (Pallas in
    interpret mode here) and the forced NumPy mirrors leave identical
    fingerprints; a different workload changes them."""
    from repro.launch.smoke import (fingerprint_diff, forced_impl,
                                    ledger_fingerprint, run_ledger)
    from repro.kernels.factory import resolve_impl
    assert resolve_impl("batch_seal") == "pallas"
    auto = ledger_fingerprint(run_ledger(300.0, 3.0, 5000))
    with forced_impl("numpy"):
        assert resolve_impl("batch_seal") == "numpy"
        mirror = ledger_fingerprint(run_ledger(300.0, 3.0, 5000))
    assert fingerprint_diff(auto, mirror) == []
    other = ledger_fingerprint(run_ledger(300.0, 3.0, 5000, seed=1))
    assert {"state_root", "state_words_digest", "window_roots",
            "batch_digests", "events"} <= set(fingerprint_diff(auto, other))


def test_smoke_fl_checks(tiny_world):
    """The smoke's FL checks on a tiny cohort: the node's megastep merge
    and the Eq. 4 distances agree with ref.py, a corrupted merge is
    caught, and the malicious trainer ends lowest."""
    import jax
    from repro.core.aggregation import weighted_average_tree_mega
    from repro.launch.smoke import agg_errors, malicious_lowest, run_fl
    model, opt, val, bf, eval_fn, _ = tiny_world
    node, sch, res = run_fl(model, opt, eval_fn, val, bf, n_trainers=4,
                            n_tasks=1, rounds=2)
    assert set(res) == {"task0"} and sch.mega_windows > 0
    assert weighted_average_tree_mega._cache_size() > 0
    errs = agg_errors(sch)
    assert [e["ok"] for e in errs] == [True]
    assert malicious_lowest(node)
    rt = sch.runtimes[0]
    rt.params = jax.tree.map(lambda x: x + 1.0, rt.params)
    assert not agg_errors(sch)[0]["ok"]
