"""Reputation model (Eq. 2-10): unit + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:   # degrade: property tests skip, the rest still run
    from conftest import given, settings, st  # noqa: F401

from repro.core.reputation import (ReputationParams, end_of_multitask_update,
                                   end_of_task_update, init_book,
                                   model_distances, normalised_distances,
                                   objective_reputation, subjective_opinion,
                                   tenure_weight, update_reputation)

P = ReputationParams()


# -- Eq. 4 / Eq. 3 --------------------------------------------------------------
@pytest.mark.parametrize("n,P,dt", [
    (5, 257, jnp.float32),
    (4, 1000, jnp.float32),
    (8, 5000, jnp.bfloat16),
    (1, 128, jnp.float32),
])
def test_model_distance_matches_numpy(n, P, dt):
    """Eq. 4 as settlement runs it (f32 sums, bf16 inputs widened)."""
    rng = np.random.default_rng(n * 10_000 + P)
    local = jnp.asarray(rng.normal(size=(n, P)), dt)
    glob = jnp.asarray(rng.normal(size=(P,)), dt)
    got = model_distances(local, glob)
    want = np.linalg.norm(np.asarray(local, np.float64)
                          - np.asarray(glob, np.float64)[None], axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_normalised_distance_unit_max():
    d = jnp.array([1.0, 2.0, 4.0])
    nd = normalised_distances(d)
    assert float(jnp.max(nd)) == pytest.approx(1.0)
    np.testing.assert_allclose(nd, [0.25, 0.5, 1.0])


# -- Eq. 2 -----------------------------------------------------------------------
@settings(max_examples=100, deadline=None)
@given(score=st.floats(0, 1), vc=st.integers(0, 10),
       nd=st.floats(0, 1))
def test_objective_reputation_bounds(score, vc, nd):
    o = objective_reputation(jnp.array([score]), jnp.array([float(vc)]),
                             jnp.array([10.0]), jnp.array([nd, 0.1]))
    assert 0.0 <= float(o[0]) <= 1.0


def test_objective_reputation_penalties():
    # below-threshold distance: no penalty
    full = objective_reputation(jnp.array([0.9, 0.9]), jnp.array([10., 10.]),
                                jnp.array([10., 10.]),
                                jnp.array([0.1, 1.0]),
                                ReputationParams(tau=0.5))
    assert float(full[0]) == pytest.approx(0.9, abs=1e-6)   # nd < tau
    assert float(full[1]) < 0.9                              # nd = 1 -> max penalty
    # missing rounds scales linearly
    half = objective_reputation(jnp.array([0.9]), jnp.array([5.0]),
                                jnp.array([10.0]), jnp.array([0.0, 1.0])[:1],
                                ReputationParams(tau=0.5))
    assert float(half[0]) == pytest.approx(0.45, abs=1e-6)


# -- Eq. 5-7 ---------------------------------------------------------------------
@settings(max_examples=100, deadline=None)
@given(st.lists(st.booleans(), min_size=1, max_size=12),
       st.floats(0.01, 1.0))
def test_opinion_simplex(goods, i_f):
    """b + d + u == 1 and all components in [0, 1]."""
    n = len(goods)
    good = jnp.asarray([[1.0 if g else 0.0 for g in goods]])
    ages = jnp.asarray([[float(i) for i in range(n)]])
    b, d, u = subjective_opinion(good, ages, jnp.array([i_f * 10]),
                                 jnp.array([10.0]))
    for v in (b, d, u):
        assert 0.0 - 1e-6 <= float(v[0]) <= 1.0 + 1e-6
    assert float(b[0] + d[0] + u[0]) == pytest.approx(1.0, abs=1e-5)


def test_bad_weighs_more_than_good():
    """theta < 0.5: with an even good/bad history, disbelief outweighs
    belief (the paper's anti-malice asymmetry, Eq. 6)."""
    ages = jnp.asarray([[0.0, 1.0]])
    inter = jnp.array([10.0]), jnp.array([10.0])
    # recent bad, older good — and the symmetric opposite
    b1, d1, _ = subjective_opinion(jnp.asarray([[0.0, 1.0]]), ages, *inter)
    b2, d2, _ = subjective_opinion(jnp.asarray([[1.0, 0.0]]), ages, *inter)
    assert float(d1[0]) > float(b1[0])   # bad outweighs good at equal count
    assert float(d2[0]) > 0.0
    # even when the good interaction is the recent one, theta<0.5 keeps
    # disbelief competitive
    assert float(d2[0]) > float(b2[0]) * 0.5


# -- Eq. 9-10 --------------------------------------------------------------------
@settings(max_examples=100, deadline=None)
@given(st.floats(0, 1), st.floats(0, 1), st.integers(0, 100))
def test_update_bounds_and_asymmetry(r_prev, l_rep, n_tasks):
    r = update_reputation(jnp.array([r_prev]), jnp.array([l_rep]),
                          jnp.array([float(n_tasks)]))
    assert 0.0 - 1e-6 <= float(r[0]) <= 1.0 + 1e-6
    # convexity: result between r_prev and l_rep
    lo, hi = min(r_prev, l_rep), max(r_prev, l_rep)
    assert lo - 1e-5 <= float(r[0]) <= hi + 1e-5


def test_tenure_monotone():
    n = jnp.arange(0, 50, dtype=jnp.float32)
    w = tenure_weight(n)
    assert float(w[0]) == pytest.approx(0.0)
    assert np.all(np.diff(np.asarray(w)) >= 0)
    assert float(w[-1]) < 1.0


def test_bad_behaviour_amplified_below_rmin():
    """Below R_min the update weighs L_rep harder (mistakes punished)."""
    params = ReputationParams(r_min=0.4, lam=0.5)
    n = jnp.array([20.0])
    up = update_reputation(jnp.array([0.8]), jnp.array([0.41]), n, params)
    down = update_reputation(jnp.array([0.8]), jnp.array([0.39]), n, params)
    # the 0.02 drop in L_rep crossing R_min causes a discontinuous plunge
    assert float(up[0]) - float(down[0]) > 0.2


# -- full pipeline -----------------------------------------------------------------
def test_end_of_task_profiles():
    book = init_book(3)
    rng = np.random.default_rng(0)
    for _ in range(12):
        score = jnp.array([0.92, 0.03, 0.7])
        completed = jnp.array([10.0, 10.0, 5.0])
        dist = jnp.array([0.5, 5.0, 1.0])
        book, diag = end_of_task_update(book, score, completed,
                                        jnp.full(3, 10.0), dist, jnp.ones(3))
    rep = np.asarray(book.reputation)
    assert rep[0] > 0.7 and rep[1] < 0.25 and rep[1] < rep[2] < rep[0]
    for v in jax.tree.leaves(diag):
        assert np.all(np.isfinite(np.asarray(v)))


def _random_task_rows(rng, k, n):
    score = rng.uniform(0.0, 1.0, (k, n)).astype(np.float32)
    completed = rng.integers(0, 11, (k, n)).astype(np.float32)
    dist = rng.uniform(0.1, 5.0, (k, n)).astype(np.float32)
    part = (rng.random((k, n)) > 0.3).astype(np.float32)
    part[:, 0] = 1.0                       # overlap: trainer0 in every task
    return score, completed, np.full((k, n), 10.0, np.float32), dist, part


def test_multitask_update_matches_sequential():
    """Fused K-task settlement == K sequential end_of_task_update calls
    (same row order), including overlapping participation masks."""
    rng = np.random.default_rng(7)
    k, n = 4, 6
    score, completed, total, dist, part = _random_task_rows(rng, k, n)

    seq_book = init_book(n)
    seq_diags = []
    for j in range(k):
        seq_book, d = end_of_task_update(
            seq_book, jnp.asarray(score[j]), jnp.asarray(completed[j]),
            jnp.asarray(total[j]), jnp.asarray(dist[j]),
            jnp.asarray(part[j]))
        seq_diags.append(d)

    fused_book, diags = end_of_multitask_update(
        init_book(n), score, completed, total, dist, part)

    for a, b in zip(jax.tree.leaves(seq_book), jax.tree.leaves(fused_book)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
    for key in ("o_rep", "s_rep", "l_rep"):
        want = np.stack([np.asarray(d[key]) for d in seq_diags])
        np.testing.assert_allclose(np.asarray(diags[key]), want, rtol=1e-5,
                                   atol=1e-6)


def test_multitask_update_single_row_matches_single_task():
    rng = np.random.default_rng(3)
    n = 5
    score, completed, total, dist, part = _random_task_rows(rng, 1, n)
    book_a, diag_a = end_of_task_update(
        init_book(n), jnp.asarray(score[0]), jnp.asarray(completed[0]),
        jnp.asarray(total[0]), jnp.asarray(dist[0]), jnp.asarray(part[0]))
    book_b, diag_b = end_of_multitask_update(
        init_book(n), score, completed, total, dist, part)
    for a, b in zip(jax.tree.leaves(book_a), jax.tree.leaves(book_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)
    np.testing.assert_allclose(np.asarray(diag_a["s_rep"]),
                               np.asarray(diag_b["s_rep"][0]), rtol=1e-6,
                               atol=1e-7)


def test_non_participants_unchanged():
    book = init_book(4)
    before = np.asarray(book.reputation).copy()
    part = jnp.array([1.0, 1.0, 0.0, 0.0])
    book, _ = end_of_task_update(book, jnp.full(4, 0.9), jnp.full(4, 10.0),
                                 jnp.full(4, 10.0),
                                 jnp.array([1.0, 1.0, 1.0, 1.0]), part)
    after = np.asarray(book.reputation)
    np.testing.assert_allclose(after[2:], before[2:])
    assert after[0] != before[0]
