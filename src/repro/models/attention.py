"""GQA attention: blocked-causal prefill/train path, KV-cache decode path.

Design notes (TPU adaptation):
  * The train/prefill path never materialises the (S, S) score matrix.  It
    scans over (q_chunk, kv_chunk<=q_chunk) pairs — a flash-attention-shaped
    schedule expressed at the XLA level so the dry-run cost analysis stays
    causal-honest (~S^2/2, not S^2).  The Pallas `flash_attention` kernel
    (kernels/flash_attention.py) implements the same schedule; no model
    path calls it.
  * Decode reads a (B, S_max, Hkv, dh) KV cache; for long-context cells the
    cache seq dim is sharded over the `model` axis (KV-SP) and the softmax
    normaliser is combined across shards by GSPMD-inserted collectives.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.layers import apply_mrope, apply_rope, dense_init, rms_norm

NEG_INF = -1e30


def init_attn_params(key, cfg, dtype, cross=False):
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, qd), dtype),
        "wk": dense_init(ks[1], (d, kvd), dtype),
        "wv": dense_init(ks[2], (d, kvd), dtype),
        "wo": dense_init(ks[3], (qd, d), dtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((qd,), dtype)
        p["bk"] = jnp.zeros((kvd,), dtype)
        p["bv"] = jnp.zeros((kvd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), dtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), dtype)
    return p


def _project_qkv(cfg, p, x, positions, rope: bool):
    B, S, _ = x.shape
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    k = jnp.einsum("bsd,de->bse", x, p["wk"])
    v = jnp.einsum("bsd,de->bse", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, Hkv, dh)
    v = v.reshape(B, S, Hkv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if rope and cfg.rope_variant == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif rope and cfg.rope_variant == "mrope":
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    return q, k, v


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    B, S, Hkv, dh = k.shape
    return jnp.repeat(k, n_rep, axis=2)


# ---------------------------------------------------------------------------
# Blocked causal attention (train / prefill)
# ---------------------------------------------------------------------------
def blocked_causal_attention(q, k, v, chunk: int, ctx=None):
    """Online-softmax attention over (q_chunk, kv_chunk<=q_chunk) pairs.

    q: (B, S, H, dh); k, v: (B, S, Hkv, dh).  Returns (B, S, H, dh).
    FLOPs ~ B*H*S^2*dh (causal half counted exactly: T*(T+1)/2 chunk pairs).
    """
    B, S, H, dh = q.shape
    Hkv = k.shape[2]
    n_rep = H // Hkv
    if S % chunk != 0:
        chunk = S  # degenerate small-seq fallback
    T = S // chunk
    scale = dh ** -0.5

    qc = q.reshape(B, T, chunk, H, dh)
    kc = k.reshape(B, T, chunk, Hkv, dh)
    vc = v.reshape(B, T, chunk, Hkv, dh)

    # enumerate the lower-triangular chunk pairs statically
    pairs = [(qi, ki) for qi in range(T) for ki in range(qi + 1)]
    pairs = jnp.asarray(pairs, jnp.int32)  # (n_pairs, 2)

    # accumulators carried across the scan: per q-chunk online softmax state
    acc = jnp.zeros((B, T, chunk, H, dh), jnp.float32)
    row_max = jnp.full((B, T, chunk, H), NEG_INF, jnp.float32)
    row_sum = jnp.zeros((B, T, chunk, H), jnp.float32)

    local_mask = jnp.tril(jnp.ones((chunk, chunk), bool))

    @jax.checkpoint
    def body(carry, pair):
        acc, row_max, row_sum = carry
        qi, ki = pair[0], pair[1]
        qb = jax.lax.dynamic_index_in_dim(qc, qi, 1, keepdims=False)
        kb = jax.lax.dynamic_index_in_dim(kc, ki, 1, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(vc, ki, 1, keepdims=False)
        kb = _repeat_kv(kb, n_rep)
        vb = _repeat_kv(vb, n_rep)
        s = jnp.einsum("bqhd,bkhd->bqhk", qb.astype(jnp.float32),
                       kb.astype(jnp.float32)) * scale
        diag = qi == ki
        s = jnp.where(jnp.logical_or(~diag, local_mask[None, :, None, :]),
                      s, NEG_INF)
        m_prev = jax.lax.dynamic_index_in_dim(row_max, qi, 1, keepdims=False)
        l_prev = jax.lax.dynamic_index_in_dim(row_sum, qi, 1, keepdims=False)
        a_prev = jax.lax.dynamic_index_in_dim(acc, qi, 1, keepdims=False)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        a_new = a_prev * corr[..., None] + jnp.einsum(
            "bqhk,bkhd->bqhd", p, vb.astype(jnp.float32))
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, qi, 1)
        row_max = jax.lax.dynamic_update_index_in_dim(row_max, m_new, qi, 1)
        row_sum = jax.lax.dynamic_update_index_in_dim(row_sum, l_new, qi, 1)
        return (acc, row_max, row_sum), None

    (acc, row_max, row_sum), _ = jax.lax.scan(body, (acc, row_max, row_sum), pairs)
    out = acc / jnp.maximum(row_sum[..., None], 1e-30)
    return out.reshape(B, S, H, dh).astype(q.dtype)


def full_causal_attention(q, k, v):
    """Reference dense path for tiny smoke shapes."""
    B, S, H, dh = q.shape
    n_rep = H // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * dh ** -0.5
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_block(cfg, p, x, positions, ctx=None, chunk=1024,
                    return_cache=False):
    """Full attention sub-block (projections + mixing + output).

    Returns out, or (out, (k, v)) when ``return_cache`` (prefill path —
    avoids re-projecting K/V a second time for the cache).
    """
    q, k, v = _project_qkv(cfg, p, x, positions, rope=True)
    if ctx is not None:
        # SP->TP transition happens HERE, once per layer: q/k/v become
        # heads-sharded and seq-replicated BEFORE the chunk reshape.
        # Without this, GSPMD re-gathers the seq-sharded tensors inside
        # every (q_chunk, kv_chunk) scan step — measured 2.06 TB/chip of
        # a 2.82 TB total on moonshot train_4k (see EXPERIMENTS.md §Perf).
        q = ctx.act_heads(q)
        if ctx.sp_axis is not None:
            # only needed when the residual stream is seq-sharded; on
            # non-SP archs with few KV heads it forces padding gathers
            # (measured -8% on qwen2-0.5b, GQA kv=2 over 16-way TP)
            k, v = ctx.act_heads(k), ctx.act_heads(v)
    S = x.shape[1]
    if S <= 2 * chunk:
        o = full_causal_attention(q, k, v)
    else:
        o = blocked_causal_attention(q, k, v, chunk, ctx)
    if ctx is not None:
        o = ctx.act_heads(o)
    B = x.shape[0]
    o = o.reshape(B, S, cfg.q_dim)
    out = jnp.einsum("be,ed->bd", o.reshape(-1, cfg.q_dim), p["wo"]).reshape(
        B, S, cfg.d_model)
    if return_cache:
        return out, (k, v)
    return out


# ---------------------------------------------------------------------------
# Decode path (KV cache)
# ---------------------------------------------------------------------------
def init_kv_cache(cfg, batch, max_len, n_layers, dtype):
    Hkv, dh = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((n_layers, batch, max_len, Hkv, dh), dtype),
        "v": jnp.zeros((n_layers, batch, max_len, Hkv, dh), dtype),
    }


def decode_attention_block(cfg, p, x, cache_k, cache_v, pos, ctx=None):
    """One-token decode: x (B, 1, d); cache_{k,v} (B, S_max, Hkv, dh).

    ``pos`` is the current write index (scalar int32).  Returns
    (out (B,1,d), new_k, new_v).
    """
    B = x.shape[0]
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32)
    if cfg.rope_variant == "mrope":
        positions = jnp.broadcast_to(pos, (3, B, 1)).astype(jnp.int32)
    q, k, v = _project_qkv(cfg, p, x, positions, rope=True)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, pos, axis=1)
    if ctx is not None:
        cache_k = ctx.constrain(cache_k, ctx.kv_cache_spec())
        cache_v = ctx.constrain(cache_v, ctx.kv_cache_spec())

    n_rep = H // Hkv
    S = cache_k.shape[1]
    qh = q.reshape(B, H, dh)
    kk = cache_k.reshape(B, S, Hkv, 1, dh)
    s = jnp.einsum("bskrd,bkrd->bskr",
                   jnp.broadcast_to(kk, (B, S, Hkv, n_rep, dh)).astype(jnp.float32),
                   qh.reshape(B, Hkv, n_rep, dh).astype(jnp.float32)) * dh ** -0.5
    valid = (jnp.arange(S, dtype=jnp.int32) <= pos)[None, :, None, None]
    s = jnp.where(valid, s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=1)
    vv = jnp.broadcast_to(cache_v.reshape(B, S, Hkv, 1, dh),
                          (B, S, Hkv, n_rep, dh)).astype(jnp.float32)
    o = jnp.einsum("bskr,bskrd->bkrd", pattn, vv).reshape(B, 1, cfg.q_dim)
    out = jnp.einsum("bsd,de->bse", o.astype(x.dtype), p["wo"])
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# Cross attention (whisper decoder)
# ---------------------------------------------------------------------------
def cross_attention_block(cfg, p, x, enc_k, enc_v, ctx=None):
    """x: (B, S, d); enc_{k,v}: (B, S_enc, Hkv, dh) precomputed from encoder."""
    B, S, _ = x.shape
    H, dh = cfg.n_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(B, S, H, dh)
    n_rep = H // enc_k.shape[2]
    k = _repeat_kv(enc_k, n_rep)
    v = _repeat_kv(enc_v, n_rep)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * dh ** -0.5
    pattn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", pattn, v.astype(jnp.float32))
    o = o.reshape(B, S, cfg.q_dim).astype(x.dtype)
    return jnp.einsum("bse,ed->bsd", o, p["wo"])


def encode_cross_kv(cfg, p, enc_out):
    """Precompute decoder cross-attn K/V from encoder output."""
    B, S, _ = enc_out.shape
    k = jnp.einsum("bsd,de->bse", enc_out, p["wk"]).reshape(
        B, S, cfg.n_kv_heads, cfg.head_dim)
    v = jnp.einsum("bsd,de->bse", enc_out, p["wv"]).reshape(
        B, S, cfg.n_kv_heads, cfg.head_dim)
    return k, v


def bidir_attention_block(cfg, p, x, ctx=None):
    """Encoder self-attention (no mask, no rope for whisper)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, None, rope=False)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * cfg.head_dim ** -0.5
    pattn = jax.nn.softmax(s, axis=-1)
    o = pattn @ jnp.moveaxis(v.astype(jnp.float32), 1, 2)  # (B,h,q,dh)
    o = jnp.moveaxis(o, 1, 2).reshape(B, S, cfg.q_dim).astype(x.dtype)
    return jnp.einsum("bse,ed->bsd", o, p["wo"])
