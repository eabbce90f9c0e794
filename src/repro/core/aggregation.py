"""Reputation-weighted aggregation (paper Eq. 1): w_g = sum(s_i w_i) / sum(s_i).

Three call paths:
  * stacked        — trainers on a leading axis (oracle / CPU FL path);
                     optionally dispatched to the Pallas `weighted_agg` kernel.
  * mesh-sharded   — trainers mapped to the mesh `data`(x`pod`) axes; the
                     aggregation is a weighted psum (the rollup commit).
  * pytree         — convenience wrapper over full parameter pytrees.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def weighted_average_flat(stacked: jnp.ndarray, scores: jnp.ndarray,
                          use_pallas: bool = False) -> jnp.ndarray:
    """stacked: (n, P) trainer weights; scores: (n,) -> (P,)."""
    if use_pallas:
        from repro.kernels.ops import weighted_agg
        return weighted_agg(stacked, scores)
    s = scores.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(s), 1e-12)
    # full f32 products: at the TPU's default precision one bf16 pass
    # rounds every weight to 8 bits, an error as large as one trainer's
    # share of a 128-trainer merge; the merge is bound by reading the
    # weights, so the extra passes cost nothing measurable
    return (jnp.einsum("np,n->p", stacked.astype(jnp.float32), s,
                       precision=jax.lax.Precision.HIGHEST)
            / denom).astype(stacked.dtype)


def weighted_average_tree(stacked_tree, scores, use_pallas: bool = False):
    """Pytree whose leaves carry a leading trainer axis."""
    def leaf(x):
        flat = x.reshape(x.shape[0], -1)
        out = weighted_average_flat(flat, scores, use_pallas)
        return out.reshape(x.shape[1:])
    return jax.tree.map(leaf, stacked_tree)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def weighted_average_tree_jit(stacked_tree, scores, use_pallas: bool = False):
    """Fused form of ``weighted_average_tree`` (one dispatch per round
    instead of ~3 eager ops per leaf) — the scheduler hot path."""
    return weighted_average_tree(stacked_tree, scores, use_pallas)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def weighted_average_tree_mega(stacked_trees, scores,
                               use_pallas: bool = False):
    """T Eq. 1 aggregations as ONE dispatch: leaves carry (T, n, ...) and
    ``scores`` is (T, n).  Row t is bit-exact equal to
    ``weighted_average_tree_jit`` on task t alone — each task's reduction
    is element-wise independent along the new axis (the cross-task
    megastep path; see fl/scheduler.py)."""
    return jax.vmap(lambda t, s: weighted_average_tree(t, s, use_pallas))(
        stacked_trees, scores)


def weighted_psum_tree(local_tree, score, axis_names):
    """Mesh path: each `data`-axis group holds ONE trainer's params.

    local_tree: this trainer's params; score: this trainer's scalar score.
    Returns the Eq. 1 average, identical on all groups (one weighted
    all-reduce over ``axis_names`` — this is the rollup 'commit').
    """
    denom = jax.lax.psum(score.astype(jnp.float32), axis_names)

    def leaf(x):
        num = jax.lax.psum(x.astype(jnp.float32) * score.astype(jnp.float32),
                           axis_names)
        return (num / jnp.maximum(denom, 1e-12)).astype(x.dtype)
    return jax.tree.map(leaf, local_tree)


def tree_sub(a, b):
    return jax.tree.map(lambda x, y: x - y, a, b)


def tree_add(a, b):
    return jax.tree.map(lambda x, y: x + y, a, b)


def tree_flat(tree):
    leaves = jax.tree.leaves(tree)
    return jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])


def tree_flat_stacked(tree):
    """Flatten a pytree whose leaves carry a leading trainer axis to (n, P)
    — the batched counterpart of ``tree_flat`` (one Eq. 4 distance pass for
    a whole cohort instead of per-trainer flattens)."""
    leaves = jax.tree.leaves(tree)
    return jnp.concatenate(
        [l.reshape(l.shape[0], -1).astype(jnp.float32) for l in leaves],
        axis=1)
