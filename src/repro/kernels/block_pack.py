"""Block-packing kernel: N gas-limited FIFO blocks in one compiled scan.

``VectorChain.produce_block`` packs ONE block with two ``searchsorted``
calls (head-of-line eligibility on the running-max submit times, then the
gas cap on the gas cumsum).  The fused window loop (core/fused.py) needs
the SAME packing decision for every block of a run at once; the carried
mempool pointer makes the blocks sequentially dependent, so this module
lowers the whole loop into one ``lax.scan`` (jax impl) instead of N
Python round-trips.

Bit-exactness across backends: the eligibility compare is on float64
submit times and the gas cap on int64 cumsums — neither survives a
float32 downcast (JAX_ENABLE_X64=0) or a TPU (no f64).  The device
impl therefore binary-searches on a **monotone (hi, lo) u32 pair
encoding**: for non-negative IEEE doubles the raw bit pattern orders
exactly like the value, and a non-negative int64 splits into ordered
u32 halves, so the pair-lexicographic compare reproduces the NumPy
float64/int64 ``searchsorted`` decisions bit-for-bit on every backend.

``block_pack_np`` is the bit-exact NumPy mirror (the per-block
``produce_block`` semantics, pinned equal by tests/test_kernels.py);
both impls are registered with ``kernels.factory`` under op
``"block_pack"``.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np


def _split_f64(x: np.ndarray):
    """Monotone (hi, lo) u32 encoding of non-negative float64 values."""
    x = np.ascontiguousarray(x, np.float64)
    assert x.size == 0 or float(x.min()) >= 0.0, \
        "pair encoding requires non-negative times"
    bits = x.view(np.uint64)
    return (bits >> np.uint64(32)).astype(np.uint32), \
        (bits & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def _split_i64(x: np.ndarray):
    """Monotone (hi, lo) u32 encoding of non-negative int64 values."""
    x = np.ascontiguousarray(x, np.int64)
    assert x.size == 0 or int(x.min()) >= 0, \
        "pair encoding requires non-negative gas"
    u = x.view(np.uint64)
    return (u >> np.uint64(32)).astype(np.uint32), \
        (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def _bucket(n: int, floor: int = 16) -> int:
    """Next power-of-two size >= n (shape bucketing keeps the jit cache
    small: one compile per bucket, not one per run length)."""
    return max(floor, 1 << max(0, (int(n) - 1).bit_length()))


# -- NumPy mirror (THE reference semantics: produce_block per block) --------

def block_pack_np(tmax: np.ndarray, gcum: np.ndarray, times: np.ndarray,
                  n_vis: np.ndarray, gas_limit: int,
                  ptr0: int) -> np.ndarray:
    """Pack ``len(times)`` consecutive blocks; returns the per-block FIFO
    stop pointers (int64).

    tmax:  (N,) float64 running max of submit times (arrival order)
    gcum:  (N,) int64 gas cumsum (arrival order)
    times: (B,) float64 block timestamps, nondecreasing
    n_vis: (B,) int64 mempool length visible to each block (txs staged
           before that block's ``run_until`` call)
    Block b confirms ``[stop[b-1], stop[b])`` — exactly what B successive
    ``VectorChain.produce_block(times[b])`` calls would confirm.
    """
    times = np.asarray(times, np.float64)
    n_vis = np.asarray(n_vis, np.int64)
    stops = np.empty(len(times), np.int64)
    ptr = int(ptr0)
    for b in range(len(times)):
        n = int(n_vis[b])
        hi = int(np.searchsorted(tmax[:n], times[b], side="right"))
        hi = max(hi, ptr)
        base = int(gcum[ptr - 1]) if ptr > 0 else 0
        k = int(np.searchsorted(gcum[ptr:hi], base + int(gas_limit),
                                side="right"))
        ptr += k
        stops[b] = ptr
    return stops


# -- pair-compare binary search (jnp) ----------------------------------------

def _pair_le(ah, al, bh, bl):
    return (ah < bh) | ((ah == bh) & (al <= bl))


def _search_right(hi_arr, lo_arr, vh, vl, lo0, hi0, iters: int):
    """First index i in [lo0, hi0) with arr[i] > (vh, vl); hi0 if none —
    the pair-encoded ``searchsorted(..., side="right")``."""
    n = hi_arr.shape[0]

    def body(_, lh):
        l, h = lh
        cont = l < h
        m = (l + h) // 2
        mi = jnp.minimum(m, n - 1)
        le = cont & _pair_le(hi_arr[mi], lo_arr[mi], vh, vl)
        return (jnp.where(le, m + 1, l),
                jnp.where(cont & ~le, m, h))
    l, _ = jax.lax.fori_loop(0, iters, body, (lo0, hi0))
    return l


@functools.partial(jax.jit, static_argnames=("iters",),
                   donate_argnums=(0, 1, 2, 3))
def _pack_scan(tmax_hi, tmax_lo, gcum_hi, gcum_lo, t_hi, t_lo, n_vis,
               lim_hi, lim_lo, ptr0, iters: int):
    """One ``lax.scan`` over blocks; the mempool SoA pair buffers are
    donated (consumed by this one fused program)."""
    def block(ptr, xs):
        th, tl, nv = xs
        hi_t = _search_right(tmax_hi, tmax_lo, th, tl,
                             jnp.int32(0), jnp.int32(tmax_hi.shape[0]),
                             iters)
        hi = jnp.maximum(jnp.minimum(hi_t, nv), ptr)
        pm = jnp.maximum(ptr - 1, 0)
        has = ptr > 0
        bh = jnp.where(has, gcum_hi[pm], jnp.uint32(0))
        bl = jnp.where(has, gcum_lo[pm], jnp.uint32(0))
        vl = bl + lim_lo
        vh = bh + lim_hi + (vl < bl).astype(jnp.uint32)
        stop = _search_right(gcum_hi, gcum_lo, vh, vl, ptr, hi, iters)
        return stop, stop
    _, stops = jax.lax.scan(block, jnp.asarray(ptr0, jnp.int32),
                            (t_hi, t_lo, n_vis))
    return stops


def _encode(tmax, gcum, times, n_vis, gas_limit, ptr0):
    """Host-side pair encoding + shape bucketing for the scan impl."""
    n, b = len(tmax), len(times)
    np_, bp = _bucket(n), _bucket(b)
    tmh, tml = _split_f64(tmax)
    gch, gcl = _split_i64(gcum)
    if np_ > n:   # sentinel pad: never time-eligible, never under the cap
        pad = np.full(np_ - n, 0xFFFFFFFF, np.uint32)
        tmh, tml = np.concatenate([tmh, pad]), np.concatenate([tml, pad])
        gch, gcl = np.concatenate([gch, pad]), np.concatenate([gcl, pad])
    th, tl = _split_f64(np.asarray(times, np.float64))
    nv = np.asarray(n_vis, np.int32)
    if bp > b:    # n_vis=0 tail blocks pack nothing (dropped by caller)
        zpad = np.zeros(bp - b, np.uint32)
        th, tl = np.concatenate([th, zpad]), np.concatenate([tl, zpad])
        nv = np.concatenate([nv, np.zeros(bp - b, np.int32)])
    lim = int(gas_limit)
    lim_hi = np.uint32(lim >> 32)
    lim_lo = np.uint32(lim & 0xFFFFFFFF)
    iters = max(1, np_.bit_length() + 1)
    return (tmh, tml, gch, gcl, th, tl, nv, lim_hi, lim_lo,
            np.int32(ptr0), iters)


def block_pack_jax(tmax, gcum, times, n_vis, gas_limit, ptr0) -> np.ndarray:
    """XLA impl: the whole block loop as ONE jitted ``lax.scan``."""
    enc = _encode(tmax, gcum, times, n_vis, gas_limit, ptr0)
    with warnings.catch_warnings():
        # CPU XLA cannot alias these donations; on TPU they are taken
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        stops = _pack_scan(*enc[:-1], iters=enc[-1])
    return np.asarray(stops, np.int64)[: len(times)]


def fused_scan_lowering(n_txs: int, n_blocks: int,
                        gas_limit: int = 9_000_000) -> str:
    """Compiled HLO text of the fused packing scan at a given shape
    (analysis/hlo_cost.py cost assertions; math.inf-free synthetic
    stream)."""
    n_txs, n_blocks = _bucket(n_txs), _bucket(n_blocks)
    iters = max(1, n_txs.bit_length() + 1)
    args = (jnp.zeros(n_txs, jnp.uint32), jnp.zeros(n_txs, jnp.uint32),
            jnp.zeros(n_txs, jnp.uint32), jnp.zeros(n_txs, jnp.uint32),
            jnp.zeros(n_blocks, jnp.uint32), jnp.zeros(n_blocks, jnp.uint32),
            jnp.zeros(n_blocks, jnp.int32), np.uint32(gas_limit >> 32),
            np.uint32(gas_limit & 0xFFFFFFFF), np.int32(0))
    lowered = jax.jit(functools.partial(_pack_scan, iters=iters)).lower(*args)
    return lowered.compile().as_text()
