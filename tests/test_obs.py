"""Program spans and counters (``repro.obs``) on one fused window.

One CPU-size ``FusedWindowLoop`` window over the default node, with the
chip's kernel selection (Pallas interpreted here): under
``jax.profiler.trace`` every layer span shows and nests as the layers
do (kernel call in commitment in seal); the window counts itself, its
kernel calls and the bytes each call copies to the device (the
committed words once, by the first root, then only what a window
touched); the NumPy mirrors stay unwrapped and uncounted;
the node service reports the counters under ``"node"``; a blob put
counts its path (raw array tree or pickle) and the bytes it hashed.

One CPU-size FL epoch through ``Scheduler`` (megastep): the FL spans
show, emission nests in settlement and in nothing else of the protocol,
and the counters count tasks, rounds, images trained, images the
oracles evaluated and blob puts exactly; the mega score table scored in chunks equals
the one-chunk table bit for bit, and the chunk is the largest that fits.
"""
import asyncio
import glob
import os
import pickle

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import NodeClient, NodeSpec, ServeSpec
from repro.core.engine import FnRegistry, TxArrays
from repro.core.fused import FusedWindowLoop
from repro.core.state import STATE_CHUNK_WORDS
from repro.core.storage import BlobStore
from repro.serve import NodeService

N_ACCOUNTS = 1 << 12
LAYERS = ("ledger.pool", "ledger.seal", "ledger.commit", "ledger.prove",
          "ledger.pack", "ledger.events")
KERNELS = ("ledger.kernel.batch_seal", "ledger.kernel.dirty_fold",
           "ledger.kernel.block_pack")


@pytest.fixture(autouse=True)
def _fresh_counters():
    obs.reset()
    yield
    obs.reset()


def _node(n_accounts=N_ACCOUNTS):
    client = NodeClient.from_spec(NodeSpec())
    state = client._state_arrays()
    state.ensure(n_accounts)
    client.state_root()                 # first full root: caches the words
    return client, state


def _window(client, n=300, seed=0, w=0):
    """Window ``w``: ``n`` txs over ``[w, w + 1)``, run to ``w + 1``."""
    rng = np.random.default_rng(seed)
    fns = FnRegistry(["publishTask", "submitLocalModel",
                      "calculateObjectiveRep"])
    txs = TxArrays(np.sort(rng.uniform(w, w + 1.0, n)),
                   np.full(n, 50_000, np.int64),
                   rng.integers(0, 3, n).astype(np.int32),
                   rng.integers(0, N_ACCOUNTS, n).astype(np.int32), fns)
    loop = FusedWindowLoop(client.chain, client.target)
    loop.submit(client.target, txs)
    loop.flush()
    loop.pump(w + 1.0)
    loop.run_until(w + 1.0)
    loop.execute()


def _host_spans(tdir, prefix="ledger."):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith(prefix)]
    return out


def _inside(spans, child, parent):
    """Every ``child`` span lies within some ``parent`` span."""
    kids = [s for s in spans if s[0] == child]
    outer = [s for s in spans if s[0] == parent]
    return bool(kids) and all(any(p[1] <= c[1] and c[2] <= p[2]
                                  for p in outer) for c in kids)


def test_window_spans_nest_as_the_layers(chip_selection, tmp_path):
    client, _ = _node()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("ledger.execute"):
            _window(client)
    spans = _host_spans(str(tmp_path))
    names = {s[0] for s in spans}
    assert set(LAYERS + KERNELS) <= names, names
    for name in LAYERS + KERNELS:
        assert _inside(spans, name, "ledger.execute"), name
    assert _inside(spans, "ledger.kernel.dirty_fold", "ledger.commit")
    assert _inside(spans, "ledger.commit", "ledger.seal")
    assert _inside(spans, "ledger.kernel.batch_seal", "ledger.seal")
    assert _inside(spans, "ledger.kernel.block_pack", "ledger.pack")
    # the splice is its own layer, after packing
    assert not _inside(spans, "ledger.events", "ledger.pack")


def test_one_window_counts_itself_its_kernels_and_their_bytes(
        chip_selection):
    """Over two windows: the refold keeps the committed words on the
    device, so after the first root (which uploads them once) a window
    stages only the words it touched, well under 1% of the buffer at
    2^18 accounts, and uploads nothing.  Over six: each window's
    ``pack.rows`` is the unconfirmed tail ``_n - _ptr`` at its pack."""
    client, state = _node(1 << 18)
    assert obs.counters()["kernel.uploads.dirty_fold"] == 1
    obs.reset()
    chain = client.chain
    ptr = chain._ptr                # a window packs from here to _n
    _window(client)
    c = obs.counters()
    assert c["windows"] == 1
    for op in ("batch_seal", "dirty_fold", "block_pack"):
        assert c[f"kernel.calls.{op}"] > 0, op
    assert c["pack.rows"] == chain._n - ptr > 0
    assert c["events.moved"] >= len(client.chain.events.since(0))
    words = state._commit_caches[("flat", STATE_CHUNK_WORDS)]["words"]
    _window(client, seed=1, w=1)
    c2 = obs.counters()
    assert c2["windows"] == 2
    staged = (c2["kernel.h2d_bytes.dirty_fold"]
              - c["kernel.h2d_bytes.dirty_fold"])
    assert 0 < staged < 0.01 * words.nbytes
    assert "kernel.uploads.dirty_fold" not in c2
    # a window hands the packer its unconfirmed tail, not the history
    for w in range(2, 6):
        before, ptr = obs.counters()["pack.rows"], chain._ptr
        _window(client, seed=w, w=w)
        rows = obs.counters()["pack.rows"] - before
        assert rows == chain._n - ptr > 0
    assert rows < chain._n // 4


def test_numpy_mirrors_are_not_counted(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "numpy")
    client, _ = _node()
    obs.reset()
    _window(client)
    c = obs.counters()
    assert c["windows"] == 1
    assert not [k for k in c if k.startswith("kernel.")], c


def test_counters_snapshot_and_reset():
    obs.count("windows")
    obs.count("pack.rows", 7)
    snap = obs.counters()
    obs.count("windows")
    assert snap == {"windows": 1, "pack.rows": 7}
    obs.reset()
    assert obs.counters() == {}


def test_blob_puts_count_their_path_and_bytes():
    store = BlobStore()
    tree = {"w": np.ones((3, 4), np.float32), "b": np.zeros(5, np.int64)}
    meta = {"arch": "lenet5"}
    store.put(tree)
    store.put(meta)
    assert obs.counters() == {
        "blob.tree_puts": 1, "blob.pickle_puts": 1,
        "blob.hashed_bytes": 3 * 4 * 4 + 5 * 8 + len(pickle.dumps(meta))}


def test_node_service_reports_the_counters():
    obs.count("windows", 3)

    async def run():
        svc = await NodeService(ServeSpec(node=NodeSpec())).start()
        await svc.submit("submitLocalModel", "u", at=0.0)
        await svc.finalize()
        stats = svc.stats()
        await svc.close()
        return stats

    stats = asyncio.run(run())
    assert stats["node"]["windows"] == 3
    assert stats["node"] == obs.counters()


# -- the FL protocol's spans and counters ----------------------------------------
FL_SPANS = ("fl.select", "fl.train", "fl.score", "fl.aggregate", "fl.emit",
            "fl.settle")
FL_BEHAVIORS = ("good", "good", "malicious", "lazy")


def _fl_world(n=8, steps=2, batch=4):
    import jax.numpy as jnp

    from repro.data.synthetic import gaussian_clusters
    from repro.models.mlp import TinyMLP
    from repro.optim.optimizers import OptimizerSpec, make_optimizer
    model = TinyMLP(16, 8, 10)
    opt = make_optimizer(OptimizerSpec(name="sgdm", lr=0.1, grad_clip=5.0))
    xs, ys = gaussian_clusters(256, 16, 10, seed=1)
    vx, vy = gaussian_clusters(40, 16, 10, seed=2)
    val = {"x": jnp.asarray(vx), "labels": jnp.asarray(vy)}

    def batch_fn(sel, rnd):
        idx = np.random.default_rng(rnd).integers(
            0, len(xs), (len(sel), steps, batch))
        return {"x": jnp.asarray(xs[idx]), "labels": jnp.asarray(ys[idx])}
    return model, opt, val, batch_fn, model.accuracy_fn()


def _fl_epoch(n_tasks=2, rounds=2, n=8, steps=2, batch=4, on_round=None):
    from repro.api import FLTaskSpec
    from repro.fl.cohort import CohortKernels, VectorCohort
    from repro.fl.dp import DPConfig
    from repro.fl.scheduler import Scheduler
    from repro.fl.server import AutoDFL
    model, opt, val, batch_fn, eval_fn = _fl_world(n, steps, batch)
    node = AutoDFL(model, opt, n, eval_fn, val,
                   spec=NodeSpec(trainer_funds=50.0))
    dp = DPConfig(noise_multiplier=0.05, batch_size=batch)
    kern = CohortKernels(model, opt, dp)
    sch = Scheduler(node, seal_every=1, on_round=on_round)
    behaviors = [FL_BEHAVIORS[i % 4] for i in range(n)]
    for t in range(n_tasks):
        sch.add_task(FLTaskSpec(f"t{t}", rounds=rounds, init_seed=t),
                     VectorCohort(model, opt, batch_fn, node.store,
                                  behaviors=behaviors, local_steps=steps,
                                  dp=dp, seed=t, kernels=kern))
    sch.run()
    return node, sch, behaviors


def test_fl_spans_open_and_nest(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        _, sch, _ = _fl_epoch()
    assert sch.mega_windows > 0
    spans = _host_spans(str(tmp_path), prefix="fl.")
    assert set(FL_SPANS) <= {s[0] for s in spans}
    # settlement emits calculateSubjectiveRep inside its span, the rounds
    # emit outside it; the other layers nest in none of the FL spans
    settles = [s for s in spans if s[0] == "fl.settle"]
    emits = [s for s in spans if s[0] == "fl.emit"]
    inside = [e for e in emits
              if any(p[1] <= e[1] and e[2] <= p[2] for p in settles)]
    assert inside and len(inside) < len(emits)
    for inner in ("fl.select", "fl.train", "fl.score", "fl.aggregate"):
        for outer in FL_SPANS:
            if outer != inner:
                assert not _inside(spans, inner, outer), (inner, outer)


def test_fl_counters_count_exactly():
    steps, batch, n_tasks, rounds = 2, 4, 2, 2
    recs = []
    node, sch, behaviors = _fl_epoch(n_tasks, rounds, steps=steps,
                                     batch=batch, on_round=recs.append)
    c = obs.counters()
    assert {r.task_id for r in recs} == {rt.task_id for rt in sch.runtimes}
    trained = sum(sum(behaviors[i] != "malicious" for i in rec.idxs)
                  for rec in recs)
    assert c["fl.tasks"] == n_tasks
    assert c["fl.rounds"] == n_tasks * rounds
    assert c["fl.samples"] == trained * steps * batch
    assert c["fl.eval_images"] == sum(len(r.idxs) for r in recs) * 40
    # each task-round's submissions are one raw array-tree put; the
    # scheduler's model description, one pickled put a task
    assert c["blob.tree_puts"] == len(recs)
    assert c["blob.pickle_puts"] == n_tasks


@pytest.mark.parametrize("chunk", [1, 2, 4])
def test_chunked_mega_score_table_equals_one_dispatch(chunk):
    import jax.numpy as jnp

    from repro.core.oracle import ValidationSlices, _mega_eval
    model, _, val, _, eval_fn = _fl_world()
    keys = jax.random.split(jax.random.key(0), 3 * 8)
    trees = [model.init_params(k) for k in keys]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs).reshape(
        (3, 8) + xs[0].shape), *trees)
    slices = ValidationSlices(val, 5)
    whole = np.asarray(_mega_eval(eval_fn, 8)(stacked, slices.stacked))
    parts = np.asarray(_mega_eval(eval_fn, chunk)(stacked, slices.stacked))
    assert whole.shape == (3, 5, 8)
    np.testing.assert_array_equal(parts, whole)


@pytest.mark.parametrize("n,per,budget,chunk", [
    (128, 0.25e9, 7.9e9, 16), (128, 1.0, 1e9, 128), (128, 2e9, 1e9, 1),
    (12, 1.0, 8, 6)])
def test_score_chunk_is_the_largest_divisor_that_fits(n, per, budget, chunk):
    from repro.core.oracle import score_chunk
    assert score_chunk(n, per, budget) == chunk
