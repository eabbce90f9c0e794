"""Swappable kernel factory: one registry for every ledger hot-path op,
and the one place that decides mirror or chip.

xformers-``block_factory`` shape: each op registers its interchangeable
implementations under string keys, and call sites ask the factory
instead of hard-wiring one backend:

    from repro.kernels.factory import get_kernel
    stops = get_kernel("block_pack")(tmax, gcum, times, n_vis, limit, p0)

Each op registers its NumPy mirror (``"numpy"``, the semantics of
record; the digest ops' mirrors live in ``kernels/digest.py``) and the
device impls a backend selects: ``"jax"`` (a jitted XLA program),
``"pallas"`` (the Pallas TPU kernel, interpreted off the chip) or
``"shard_map"`` (the fabric's lane fold over a device mesh).  Each
registration names the op's CPU and TPU default: the ``"auto"`` table
(docs/KERNELS.md), read by the one backend probe, ``on_tpu()``.

Every impl but the NumPy mirror stages its NumPy inputs on the device,
so ``register_kernel`` wraps it: each call runs inside a
``ledger.kernel.<op>`` span and counts ``kernel.calls.<op>`` and
``kernel.h2d_bytes.<op>`` in ``repro.obs``.  The wrapper counts the
``nbytes`` of the NumPy arguments, which such an impl copies whole; an
impl that keeps data on the device between calls (``dirty_fold`` with
its resident word buffer) registers with ``counts_h2d=True`` and counts
the bytes it actually stages itself.  The mirrors stay unwrapped.

Selection: an explicit ``impl=`` wins; else the ``REPRO_KERNEL_IMPL``
env var (tests and ``chip_smoke.py`` force the mirror with it); else
``"auto"``.  Every impl of an op takes and returns
host NumPy values with identical semantics (bit-exact, pinned by
tests/test_kernels.py), so swapping is a pure performance choice.

Adding a kernel: the mirror in ``kernels/digest.py`` (or the op's own
module), one device impl, and a row in ``_load()`` — see
docs/KERNELS.md.
"""
from __future__ import annotations

import functools
import os
from typing import Callable, Dict, Tuple

import numpy as np

from repro import obs

#: the environment variable that forces every "auto" choice to one impl
IMPL_ENV = "REPRO_KERNEL_IMPL"

_REGISTRY: Dict[str, Dict[str, Callable]] = {}
_DEFAULTS: Dict[str, Dict[str, str]] = {}      # op -> {"cpu": .., "tpu": ..}
_LOADED = False
_ON_TPU: bool | None = None


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU: the one probe of the
    backend, behind every ``"auto"`` choice and every Pallas wrapper's
    ``interpret`` default (``kernels.ops._interpret``).  Asked once per
    process; the device set cannot change mid-process.  A failing probe
    raises: answering "not a TPU" would quietly run the mirrors on the
    chip."""
    global _ON_TPU
    if _ON_TPU is None:
        import jax
        _ON_TPU = jax.default_backend() == "tpu"
    return _ON_TPU


def register_kernel(op: str, impl: str, fn: Callable, *,
                    cpu_default: bool = False,
                    tpu_default: bool = False,
                    counts_h2d: bool = False) -> Callable:
    """Register ``fn`` as implementation ``impl`` of ``op`` (a device
    impl under its span and counters, see the module docstring)."""
    _REGISTRY.setdefault(op, {})[impl] = \
        fn if impl == "numpy" else _traced(op, fn, counts_h2d)
    d = _DEFAULTS.setdefault(op, {})
    if cpu_default or "cpu" not in d:
        d["cpu"] = impl
    if tpu_default or "tpu" not in d:
        d["tpu"] = impl
    return fn


def _traced(op: str, fn: Callable, counts_h2d: bool) -> Callable:
    name = f"ledger.kernel.{op}"
    calls, h2d = f"kernel.calls.{op}", f"kernel.h2d_bytes.{op}"

    @functools.wraps(fn)
    def call(*args, **kwargs):
        obs.count(calls)
        if not counts_h2d:
            obs.count(h2d, sum(a.nbytes for a in args
                               if isinstance(a, np.ndarray)))
        with obs.span(name):
            return fn(*args, **kwargs)
    return call


def _load() -> None:
    """Lazy one-shot registration of the built-in ledger ops (their
    modules are imported on first use, not with the factory)."""
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    from repro.kernels import batch_seal as bs
    from repro.kernels import block_pack as bp
    from repro.kernels import digest as dg
    from repro.kernels import dirty_fold as df
    from repro.kernels import shard_lanes as sl

    # multi-block FIFO packing (core/fused.py window loop)
    register_kernel("block_pack", "numpy", bp.block_pack_np)
    register_kernel("block_pack", "jax", bp.block_pack_jax,
                    cpu_default=True, tpu_default=True)

    # per-batch seal digests (VectorRollup.seal segment fold)
    register_kernel("batch_seal", "numpy", dg.batch_seal_np,
                    cpu_default=True)
    register_kernel("batch_seal", "pallas", bs.batch_seal_pallas,
                    tpu_default=True)

    # K-lane segmented seal digests (core/fused.py over the sharded
    # fabric: every lane's per-batch roots / per-window update digests
    # fold in one call; "shard_map" runs the lanes over the 1-D "shard"
    # device mesh)
    register_kernel("shard_seal", "numpy", dg.shard_seal_np,
                    cpu_default=True)
    register_kernel("shard_seal", "jax", sl.shard_seal_jax,
                    tpu_default=True)
    register_kernel("shard_seal", "shard_map", sl.shard_seal_shard_map)

    # merged update-buffer digest (seal commitment; scalar u32 out)
    def _digest_pallas(words):
        import jax.numpy as jnp

        from repro.kernels.ops import rollup_digest
        return int(rollup_digest(jnp.asarray(
            np.ascontiguousarray(words, np.uint32))))

    register_kernel("rollup_digest", "numpy", dg.xor_fold_digest,
                    cpu_default=True)
    register_kernel("rollup_digest", "pallas", _digest_pallas,
                    tpu_default=True)

    # dirty-chunk refold (StateArrays incremental commitment): digests of
    # only the chunks a window touched, patched into the cached vector;
    # the device impl patches a resident copy of the word buffer
    register_kernel("dirty_fold", "numpy", dg.dirty_fold_np,
                    cpu_default=True)
    register_kernel("dirty_fold", "pallas", df.dirty_fold_pallas,
                    tpu_default=True, counts_h2d=True)


def available_impls(op: str) -> Tuple[str, ...]:
    _load()
    return tuple(sorted(_REGISTRY.get(op, {})))


def available_ops() -> Tuple[str, ...]:
    _load()
    return tuple(sorted(_REGISTRY))


def resolve_impl(op: str, impl: str | None = None) -> str:
    """The impl key ``get_kernel(op, impl)`` would return (see module
    docstring for the selection order)."""
    _load()
    if op not in _REGISTRY:
        raise KeyError(f"unknown kernel op {op!r}; "
                       f"registered: {sorted(_REGISTRY)}")
    choice = impl or os.environ.get(IMPL_ENV) or "auto"
    if choice == "auto":
        choice = _DEFAULTS[op]["tpu" if on_tpu() else "cpu"]
    return choice


def get_kernel(op: str, impl: str | None = None) -> Callable:
    """Resolve ``op`` to one implementation (see module docstring)."""
    choice = resolve_impl(op, impl)
    table = _REGISTRY[op]
    try:
        return table[choice]
    except KeyError:
        raise KeyError(f"kernel op {op!r} has no impl {choice!r}; "
                       f"available: {sorted(table)}") from None
