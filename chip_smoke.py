"""Drive the BFL node's main path once on a TPU and check what comes out.

    python chip_smoke.py             # one chip: the ledger and FL phases
    python chip_smoke.py --chips 4   # four chips: the 4-shard fabric only

Phases (one process; JAX holds the chip for all of them):

  * ledger — the Table-I ``mixed`` workload at 3000 tx/s for 20 s of
    modeled time over 100,000 senders, through the default ``NodeSpec``
    and the fused window loop until every receipt is finalized, with the
    kernel factory's TPU defaults; then the same scenario with every
    ledger op forced to its NumPy mirror.  Roots, digests, gas and events
    must be equal bit for bit.
  * fl — LeNet-5 at its published widths: 2 concurrent tasks x 8 trainers
    (good, good, malicious, lazy, ...) through ``Scheduler``, its XLA
    Eq. 1 merge and Eq. 4 distances checked against ``kernels/ref.py``
    and the malicious trainers ending with the lowest reputation.
  * fabric (``--chips 4`` only) — the FL scheduler over a
    ``ShardSpec(count=4, mesh="on")`` fabric, whose lane seals run
    ``shard_map``-ped over the 4 chips, against ``mesh="off"``.

Each phase prints the resolved impl of every kernel-factory op, every
Pallas kernel it traced and whether it ran compiled, compile and run
seconds apart, and its roots.  The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every phase
passed.  With no TPU, or with ``REPRO_KERNEL_IMPL`` set by the caller,
the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

LEDGER_RATE = 3000.0          # tx/s, the paper's Table-II load
LEDGER_DURATION = 20.0        # modeled seconds
LEDGER_SENDERS = 100_000
FL_TRAINERS = 8
FL_TASKS = 2
FL_ROUNDS = 3
SEED = 0

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class Probe:
    """Compile seconds (JAX's own compile events, persistent-cache reads
    included) and every ``pallas_call`` traced, with its interpret flag."""

    def __init__(self):
        import jax
        from jax.experimental import pallas as pl
        self.compile_s = 0.0
        self.cache_hits = 0
        self.kernels = []                  # (name, interpret, arg shapes)
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)
        real = pl.pallas_call

        def pallas_call(kernel, *a, **kw):
            call = real(kernel, *a, **kw)
            name = kw.get("name") or getattr(kernel, "__name__", "?")
            interpret = bool(kw.get("interpret", False))

            def traced(*args):
                self.kernels.append((name, interpret,
                                     [tuple(x.shape) for x in args]))
                return call(*args)
            return traced
        pl.pallas_call = pallas_call

    def _on_dur(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phase:
    """Times one phase and reports what it traced."""

    def __init__(self, probe: Probe, name: str):
        self.probe, self.name = probe, name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.probe.compile_s
        self.h0 = self.probe.cache_hits
        self.k0 = len(self.probe.kernels)
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        comp = self.probe.compile_s - self.c0
        self.kernels = self.probe.kernels[self.k0:]
        seen = {}
        for name, interp, shapes in self.kernels:
            key = (name, interp, str(shapes))
            seen[key] = seen.get(key, 0) + 1
        for (name, interp, shapes), n in seen.items():
            say(self.name, f"pallas_call {name} "
                f"{'INTERPRETED' if interp else 'compiled'} {shapes} x{n}")
        say(self.name, f"compile_s={comp:.3f} run_s={wall - comp:.3f} "
            f"wall_s={wall:.3f} cache_hits={self.probe.cache_hits - self.h0}")
        return False


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def report_impls(phase: str, *, want_device: bool) -> None:
    from repro.kernels import factory
    impls = {op: factory.resolve_impl(op) for op in factory.available_ops()}
    say(phase, f"kernel impls {impls}")
    if want_device:
        mirrors = sorted(op for op, impl in impls.items() if impl == "numpy")
        check(not mirrors, f"ops resolved to the NumPy mirror: {mirrors}")


def ledger_phase(probe: Probe) -> None:
    from repro.launch.smoke import (fingerprint_diff, forced_impl,
                                    ledger_fingerprint, run_ledger)
    args = (LEDGER_RATE, LEDGER_DURATION, LEDGER_SENDERS, SEED)
    report_impls("ledger", want_device=True)
    with Phase(probe, "ledger") as ph:
        auto = ledger_fingerprint(run_ledger(*args))
    names = {k[0] for k in ph.kernels}
    check({"batch_seal", "chunk_digests", "dirty_fold",
           "rollup_digest"} <= names,
          f"ledger kernels traced: {sorted(names)}")
    say("ledger", f"auto {auto}")
    with forced_impl("numpy"):
        report_impls("ledger-mirror", want_device=False)
        with Phase(probe, "ledger-mirror") as ph:
            mirror = ledger_fingerprint(run_ledger(*args))
    check(not ph.kernels, "the forced-mirror run traced a Pallas kernel")
    say("ledger", f"mirror {mirror}")
    diff = fingerprint_diff(auto, mirror)
    check(not diff, f"auto and mirror ledgers differ in {diff}")
    say("ledger", "auto == mirror: state root, window roots, batch "
        "digests, gas log, blocks, events")


def _lenet_world():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_config
    from repro.data.pipeline import client_batch_fn
    from repro.data.synthetic import make_mnist_like
    from repro.models import lenet
    from repro.models.model import build_model
    from repro.optim.optimizers import OptimizerSpec, make_optimizer

    cfg = get_config("lenet5")
    model = build_model(cfg)
    opt = make_optimizer(OptimizerSpec(name="sgdm", lr=0.05, grad_clip=5.0))
    xs, ys = make_mnist_like(2048, seed=1)
    val = {"images": jnp.asarray(xs[:256]), "labels": jnp.asarray(ys[:256])}
    perm = np.random.default_rng(SEED).permutation(np.arange(256, 2048))
    parts = np.array_split(perm, FL_TRAINERS)
    raw = client_batch_fn(xs, ys, parts, 32)

    def bf(c, r):
        return {k: jnp.asarray(v) for k, v in raw(c, r).items()}
    eval_fn = jax.jit(lambda p, b: lenet.accuracy(cfg, p, b))
    return model, opt, eval_fn, val, bf


def fl_phase(probe: Probe) -> None:
    import numpy as np

    from repro.core.aggregation import (weighted_average_tree_jit,
                                        weighted_average_tree_mega)
    from repro.fl.scheduler import _settle_distances
    from repro.launch.smoke import agg_errors, malicious_lowest, run_fl
    model, opt, eval_fn, val, bf = _lenet_world()
    report_impls("fl", want_device=True)
    with Phase(probe, "fl"):
        node, sch, res = run_fl(model, opt, eval_fn, val, bf,
                                n_trainers=FL_TRAINERS, n_tasks=FL_TASKS,
                                rounds=FL_ROUNDS)
        errs = agg_errors(sch)
    # the merge is the per-task or the megastep XLA program, as the
    # scheduler's window allows; the distances are one program
    programs = sorted(f.__name__ for f in (weighted_average_tree_jit,
                                           weighted_average_tree_mega,
                                           _settle_distances)
                      if f._cache_size())
    say("fl", f"merge programs compiled {programs}")
    check("_settle_distances" in programs and len(programs) > 1,
          f"FL merge programs compiled: {programs}")
    check(set(res) == {f"task{t}" for t in range(FL_TASKS)}, "FL results")
    for e in errs:
        say("fl", f"merge and distances vs ref.py {e}")
        check(e["ok"], f"merge or distances outside tolerance: {e}")
    rep = np.asarray(node.book.reputation)
    say("fl", f"reputations {np.round(rep, 4).tolist()}")
    check(malicious_lowest(node), "malicious trainers not lowest (Fig. 3)")
    say("fl", f"state_root {node.client().state_root()} "
        f"windows {len(sch.window_records)} "
        f"settlements {len(sch.settlement_records)}")


def fabric_phase(probe: Probe) -> None:
    from repro.api import NodeSpec, ShardSpec
    from repro.kernels import shard_lanes
    from repro.launch.smoke import fingerprint_diff, ledger_fingerprint, run_fl
    model, opt, eval_fn, val, bf = _lenet_world()
    real = shard_lanes.lane_fold_mapped
    spread = []

    def mapped(mesh):
        fn = real(mesh)

        def run(*a):
            out = fn(*a)
            spread.append(sorted((str(s.device), tuple(s.data.shape))
                                 for s in out.addressable_shards))
            return out
        return run
    shard_lanes.lane_fold_mapped = mapped
    prints = {}
    for mode in ("on", "off"):
        spec = NodeSpec(shards=ShardSpec(count=4, mesh=mode),
                        trainer_funds=50.0)
        with Phase(probe, f"fabric-mesh-{mode}"):
            node, sch, _ = run_fl(model, opt, eval_fn, val, bf,
                                  n_trainers=FL_TRAINERS, n_tasks=FL_TASKS,
                                  rounds=FL_ROUNDS, spec=spec)
        prints[mode] = ledger_fingerprint(node.client())
        say("fabric", f"mesh={mode} {prints[mode]}")
        if mode == "on":
            check(bool(spread), "mesh=on never ran the shard_map lane fold")
            devices = {d for d, _ in spread[-1]}
            say("fabric", f"shard_seal output shards {spread[-1]}")
            check(len(devices) == 4, f"lane fold on {len(devices)} devices")
    shard_lanes.lane_fold_mapped = real
    diff = fingerprint_diff(prints["on"], prints["off"])
    check(not diff, f"mesh on/off fabrics differ in {diff}")
    say("fabric", "mesh on == off: fabric roots, gas, events")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the 4-shard fabric phase")
    args = ap.parse_args()
    if os.environ.get("REPRO_KERNEL_IMPL"):
        print("chip_smoke: REPRO_KERNEL_IMPL is set; the smoke chooses "
              "its own kernel impls", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 1
    if args.chips == 4 and len(devices) != 4:
        print(f"chip_smoke: --chips 4 needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache
    say("setup", f"{dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {enable_compile_cache()}")
    from repro.kernels.factory import on_tpu
    check(on_tpu(), "the kernel factory's probe does not see the TPU")
    probe = Probe()
    if args.chips == 4:
        fabric_phase(probe)
    else:
        ledger_phase(probe)
        fl_phase(probe)
    interpreted = sorted({k[0] for k in probe.kernels if k[1]})
    check(not interpreted, f"kernels ran interpreted: {interpreted}")
    say("total", f"compile_s={probe.compile_s:.3f} "
        f"cache_hits={probe.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
