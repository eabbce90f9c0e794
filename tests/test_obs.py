"""Program spans and counters (``repro.obs``) on one fused window.

One CPU-size ``FusedWindowLoop`` window over the default node: under
``jax.profiler.trace`` every layer span shows and nests as the layers
do (kernel call in commitment in seal); with the device impls selected
the window counts itself, its kernel calls and the bytes each call
copies to the device (the committed words once, then only what a window
touched); the NumPy mirrors stay unwrapped and uncounted;
the node service reports the counters under ``"node"``.
"""
import asyncio
import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import NodeClient, NodeSpec, ServeSpec
from repro.core.engine import FnRegistry, TxArrays
from repro.core.fused import FusedWindowLoop
from repro.core.state import STATE_CHUNK_WORDS
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


def _window(client, n=300, seed=0):
    rng = np.random.default_rng(seed)
    fns = FnRegistry(["publishTask", "submitLocalModel",
                      "calculateObjectiveRep"])
    txs = TxArrays(np.sort(rng.uniform(0.0, 1.0, n)),
                   np.full(n, 50_000, np.int64),
                   rng.integers(0, 3, n).astype(np.int32),
                   rng.integers(0, N_ACCOUNTS, n).astype(np.int32), fns)
    loop = FusedWindowLoop(client.chain, client.target)
    loop.submit(client.target, txs)
    loop.flush()
    loop.pump(1.0)
    loop.run_until(1.0)
    loop.execute()


def _host_spans(tdir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith("ledger.")]
    return out


def _inside(spans, child, parent):
    """Every ``child`` span lies within some ``parent`` span."""
    kids = [s for s in spans if s[0] == child]
    outer = [s for s in spans if s[0] == parent]
    return bool(kids) and all(any(p[1] <= c[1] and c[2] <= p[2]
                                  for p in outer) for c in kids)


def test_window_spans_nest_as_the_layers(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "jax")
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


def test_one_window_counts_itself_its_kernels_and_their_bytes(monkeypatch):
    """Over two windows: the refold keeps the committed words on the
    device, so after the first window (which uploads them once) a window
    stages only the words it touched, well under 1% of the buffer at
    2^18 accounts."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "jax")
    client, state = _node(1 << 18)
    obs.reset()
    _window(client)
    c = obs.counters()
    assert c["windows"] == 1
    for op in ("batch_seal", "dirty_fold", "block_pack"):
        assert c[f"kernel.calls.{op}"] > 0, op
    assert c["pack.rows"] == client.chain._n > 0
    assert c["events.moved"] >= len(client.chain.events.since(0))
    words = state._commit_caches[("flat", STATE_CHUNK_WORDS)]["words"]
    _window(client, seed=1)
    c2 = obs.counters()
    assert c2["windows"] == 2
    staged = (c2["kernel.h2d_bytes.dirty_fold"]
              - c["kernel.h2d_bytes.dirty_fold"])
    assert 0 < staged < 0.01 * words.nbytes
    assert c2["kernel.uploads.dirty_fold"] <= 1


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
