"""Reputation-weighted aggregation (paper Eq. 1): w_g = sum(s_i w_i) / sum(s_i).

One XLA merge in three forms:
  * flat      — (n, P) trainer weights on a leading axis;
  * pytree    — the same per leaf of a parameter tree whose leaves carry
                the trainer axis; ``weighted_average_tree_jit`` is its one
                dispatch a round (the scheduler's per-task path);
  * megastep  — ``weighted_average_tree_mega``: every active task's merge
                in one vmapped dispatch (the scheduler's cross-task path,
                the one the FL cell runs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def weighted_average_flat(stacked: jnp.ndarray,
                          scores: jnp.ndarray) -> jnp.ndarray:
    """stacked: (n, P) trainer weights; scores: (n,) -> (P,)."""
    s = scores.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(s), 1e-12)
    # full f32 products: at the TPU's default precision one bf16 pass
    # rounds every weight to 8 bits, an error as large as one trainer's
    # share of a 128-trainer merge; the merge is bound by reading the
    # weights, so the extra passes cost nothing measurable
    return (jnp.einsum("np,n->p", stacked.astype(jnp.float32), s,
                       precision=jax.lax.Precision.HIGHEST)
            / denom).astype(stacked.dtype)


def weighted_average_tree(stacked_tree, scores):
    """Pytree whose leaves carry a leading trainer axis."""
    def leaf(x):
        flat = x.reshape(x.shape[0], -1)
        return weighted_average_flat(flat, scores).reshape(x.shape[1:])
    return jax.tree.map(leaf, stacked_tree)


@jax.jit
def weighted_average_tree_jit(stacked_tree, scores):
    """Fused form of ``weighted_average_tree`` (one dispatch per round
    instead of ~3 eager ops per leaf) — the scheduler hot path."""
    return weighted_average_tree(stacked_tree, scores)


@jax.jit
def weighted_average_tree_mega(stacked_trees, scores):
    """T Eq. 1 aggregations as ONE dispatch: leaves carry (T, n, ...) and
    ``scores`` is (T, n).  Row t is bit-exact equal to
    ``weighted_average_tree_jit`` on task t alone — each task's reduction
    is element-wise independent along the new axis (the cross-task
    megastep path; see fl/scheduler.py)."""
    return jax.vmap(weighted_average_tree)(stacked_trees, scores)


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
