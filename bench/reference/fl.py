"""Plain reference of the AutoDFL FL protocol that the FL cells run.

Imports nothing of the program under test.  It restates, one trainer at a
time (``lax.map``, never ``vmap``), in plain ``jax.numpy`` float32 at
``Precision.HIGHEST`` (NumPy float64 for the merge, the distances and the
reputation book), what one epoch of the cell does:

  * LeNet-5 as the program builds it, the common variant of LeCun et al.
    1998: 32x32x1 -> conv 6@5x5, tanh, 2x2 average pool -> conv 16@5x5
    over all 6 maps, tanh, 2x2 average pool -> FC 400-120-84-10, tanh
    between, a linear output and softmax cross-entropy (the 1998 net
    connects C3 to some maps only, gives its pooling a trainable weight
    and bias, and ends in RBF units);
  * local training: ``steps`` sgdm steps (momentum ``beta1``, the gradient
    clipped to global norm ``grad_clip`` first) on the trainer's own
    batches; the update (new params - global) is clipped to L2 norm
    ``clip_norm`` over the whole model and Gaussian noise of standard
    deviation ``noise_multiplier * clip_norm / sqrt(batch)`` is added per
    leaf; the submission is global + noised update;
  * Fig. 3 behaviours: a malicious trainer submits N(0, 0.1^2) weights
    and never advances its momentum; a lazy trainer skips a round when a
    uniform draw is not above a threshold drawn from ``lazy_skip_range``;
  * keys and draws as the cohort documents them: per task a NumPy
    generator seeded with the cohort seed draws, each round, one uniform
    and one threshold per selected trainer (selection order); per round
    ``fold_in(key(cohort seed), round)`` splits into a DP key and a fake
    key, each split once per selected trainer; a trainer's DP key splits
    once per leaf (leaves in sorted-key order);
  * trainer selection: every trainer, ranked by reputation (ties by
    index); a round's submitters are taken in trainer-index order;
  * DON (paper Sec. III-C.5): oracle ``o`` scores a submission by its
    accuracy on the o-th of ``n_oracles`` equal validation slices; a
    submission's score is the median over oracles;
  * Eq. 1 merge: sum(s_i w_i) / sum(s_i) over the round's submitters;
  * settlement (Eq. 2-10): score = the last round's score (0 for a trainer
    that skipped it), completeness = rounds submitted / rounds, Eq. 4
    distance of the last submission from the final global model (a
    trainer that skipped the last round takes the largest submitted
    distance, or 1.0), then the objective, subjective, local and overall
    reputation update in task order; escrow pays each task's reward pro
    rata to scores above 1e-6;
  * emission: per task ``publishTask`` in the first window; each round
    window, task by task, one ``submitLocalModel`` then one
    ``calculateObjectiveRep`` per submitter, and after the last round one
    ``calculateSubjectiveRep`` per selected trainer; the rollup seals each
    window into FIFO batches of ``batch_size`` whose commit gas is the
    Table-I ``commit_base`` of each function present plus
    ``commit_per_call`` per call; the epoch's batches settle in one L1
    verify and execute (the single price for one batch of at most 5).

Departures from the paper: the images are synthetic (MNIST is not in the
repository); the DON's outlier flags and quorum decide nothing here (the
node records them; every submission is merged); payouts use the final
round's score alone (the node's rule), not an average over rounds.

``compare`` turns the program's records and the reference's into counts
of mismatches, each with limit 0 (``LIMITS``).  Floating-point
comparisons are by tolerance, each with its reason (``TOL``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
FNS = ("publishTask", "submitLocalModel", "calculateObjectiveRep",
       "calculateSubjectiveRep")

#: tolerances of the float comparisons, each a bound the right program
#: stays well inside and, but for ``logit``, a wrong one leaves (values
#: from the chip runs of the cell, PERF.md section 6)
TOL = {
    # a submission: ||program - reference|| / ||reference - global in||.
    # The program's convolutions and matmuls run at the chip's default
    # f32 precision (one bf16 pass) and the trainers are vmapped, so the
    # update differs by bf16 rounding of the products; parameters or
    # momentum held in bf16 add rounding of every weight, several times
    # more
    "submission_rel": 4e-3,
    # a top-2 logit margin the chip's default precision can close: an
    # image's prediction may differ only where the reference's margin is
    # below this.  A logit moves by at most the largest logit gap, so a
    # margin by twice it: 0.011-0.013 on the chip (gaps 0.0050-0.0065),
    # 0.024 where the CPU rounds every operand to bf16 as the chip does.
    # This bound has a lower reading only: a DON scoring in bf16 reads
    # gaps of 0.009-0.023 on the CPU, inside the sound program's range,
    # so no margin tells it from the sound program (PERF.md section 6).
    # A submission scored as another's (``malicious_as_good``) leaves it.
    "logit": 0.05,
    # the Eq. 1 merge at full f32 precision against float64:
    # ||program - reference|| / ||reference - global in||; a merge that
    # leaves a trainer out, weighs them alike or rounds to bf16 lands far
    # outside it
    "merge_rel": 1e-4,
    # reputations after settlement (f32 arithmetic against float64)
    "reputation_abs": 1e-5,
    # payouts (Python floats summed in another order)
    "payout_rel": 1e-9,
}

#: every count must be 0
LIMITS = {"counts_wrong": 0, "gas_wrong": 0, "receipts_wrong": 0,
          "megastep_wrong": 0, "submissions_wrong": 0, "scores_wrong": 0,
          "merges_wrong": 0, "settlement_wrong": 0, "fig3_wrong": 0}


# -- LeNet-5 ------------------------------------------------------------------
def _conv_tanh_pool(x, w, b):
    """A 'valid' convolution written as a matmul over 5x5 patches (its
    gradient at full f32 precision compiles on the TPU, a convolution's
    does not), tanh, then 2x2 average pooling."""
    kh, kw, c, f = w.shape
    h, wd = x.shape[1] - kh + 1, x.shape[2] - kw + 1
    patches = jnp.concatenate([x[:, i:i + h, j:j + wd, :]
                               for i in range(kh) for j in range(kw)], -1)
    y = jnp.dot(patches, w.reshape(kh * kw * c, f), precision=HI)
    y = jnp.tanh(y + b)
    n, h, wd, c = y.shape
    return y.reshape(n, h // 2, 2, wd // 2, 2, c).mean(axis=(2, 4))


def logits(p, x):
    """(B, 32, 32, 1) -> (B, 10)."""
    x = _conv_tanh_pool(x, p["conv1"]["w"], p["conv1"]["b"])
    x = _conv_tanh_pool(x, p["conv2"]["w"], p["conv2"]["b"])
    x = x.reshape(x.shape[0], -1)
    x = jnp.tanh(jnp.dot(x, p["fc1"]["w"], precision=HI) + p["fc1"]["b"])
    x = jnp.tanh(jnp.dot(x, p["fc2"]["w"], precision=HI) + p["fc2"]["b"])
    return jnp.dot(x, p["fc3"]["w"], precision=HI) + p["fc3"]["b"]


def loss(p, x, y):
    lo = logits(p, x)
    return jnp.mean(jax.nn.logsumexp(lo, axis=-1)
                    - jnp.take_along_axis(lo, y[:, None], axis=-1)[:, 0])


def _norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(l))
                        for l in jax.tree.leaves(tree)))


# -- one trainer's round -------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Hyper:
    lr: float
    beta1: float
    grad_clip: float
    clip_norm: float
    sigma: float                 # DP noise standard deviation


def _train_one(hp: Hyper, params, m, xs, ys, dp_key, fake_key):
    """One trainer: returns (trained submission, new momentum, fake)."""
    p = params
    for s in range(xs.shape[0]):
        g = jax.grad(loss)(p, xs[s], ys[s])
        scale = jnp.minimum(1.0, hp.grad_clip
                            / jnp.maximum(_norm(g), 1e-12))
        m = jax.tree.map(lambda mm, gg: hp.beta1 * mm + gg * scale, m, g)
        p = jax.tree.map(lambda pp, mm: pp - hp.lr * mm, p, m)
    upd = jax.tree.map(lambda a, b: a - b, p, params)
    scale = jnp.minimum(1.0, hp.clip_norm / jnp.maximum(_norm(upd), 1e-12))
    leaves, tdef = jax.tree.flatten(upd)
    keys = jax.random.split(dp_key, len(leaves))
    noised = [l * scale + hp.sigma * jax.random.normal(k, l.shape,
                                                        jnp.float32)
              for l, k in zip(leaves, keys)]
    sub = jax.tree.map(lambda g0, u: g0 + u, params,
                       jax.tree.unflatten(tdef, noised))
    fake = jax.tree.map(
        lambda l: jax.random.normal(fake_key, l.shape, jnp.float32) * 0.1,
        params)
    return sub, m, fake


@functools.partial(jax.jit, static_argnums=0)
def _train_round(hp, params, m, xs, ys, dp_keys, fake_keys):
    """Every selected trainer, one after another (``lax.map``)."""
    return jax.lax.map(
        lambda a: _train_one(hp, params, *a), (m, xs, ys, dp_keys,
                                               fake_keys))


@jax.jit
def _score_one(p, vx, vy, tol):
    """Per oracle: images right, and images whose top-2 logit margin is
    below ``tol`` (a prediction the chip's rounding may flip)."""
    lo = logits(p, vx.reshape((-1,) + vx.shape[2:]))
    top2 = jax.lax.top_k(lo, 2)[0]
    right = (jnp.argmax(lo, -1) == vy.reshape(-1)).reshape(vy.shape)
    close = (top2[:, 0] - top2[:, 1] < tol).reshape(vy.shape)
    return right.sum(-1), close.sum(-1)


@jax.jit
def _score_all(stacked, vx, vy, tol):
    return jax.lax.map(lambda p: _score_one(p, vx, vy, tol), stacked)


def round_keys(cohort_seed: int, rnd: int, k: int):
    key = jax.random.fold_in(jax.random.key(cohort_seed), np.uint32(rnd))
    k_dp, k_fake = jax.random.split(key)
    return jax.random.split(k_dp, k), jax.random.split(k_fake, k)


# -- the protocol around training ---------------------------------------------
def behaviors(cycle: Sequence[str], n: int) -> np.ndarray:
    return np.array([cycle[i % len(cycle)] for i in range(n)])


def selection(reputation: np.ndarray) -> np.ndarray:
    """Every trainer, by reputation descending, ties by index."""
    return np.argsort(-np.asarray(reputation, np.float64), kind="stable")


def participation(cohort_seed: int, rounds: int, sel: np.ndarray,
                  lazy: np.ndarray, lazy_range) -> List[np.ndarray]:
    """Per round, the selection positions that submit."""
    rng = np.random.default_rng(cohort_seed)
    out = []
    for _ in range(rounds):
        r = rng.random(len(sel))
        u = rng.uniform(lazy_range[0], lazy_range[1], len(sel))
        out.append(~lazy[sel] | (r > u))
    return out


def quorum(table: np.ndarray) -> np.ndarray:
    return np.median(np.asarray(table, np.float64), axis=0)


def merge(stacked: Dict, scores: np.ndarray) -> Dict:
    """Eq. 1 in float64."""
    s = np.asarray(scores, np.float64)
    den = max(float(s.sum()), 1e-12)
    return jax.tree.map(
        lambda l: np.tensordot(s, np.asarray(l, np.float64), 1) / den,
        stacked)


def _pad(leaf, k: int) -> np.ndarray:
    leaf = np.asarray(leaf)
    return np.concatenate(
        [leaf, np.zeros((k - leaf.shape[0],) + leaf.shape[1:], leaf.dtype)])


def _flat(tree, lead: int = 0) -> np.ndarray:
    leaves = jax.tree.leaves(tree)
    if lead:
        return np.concatenate([np.asarray(l, np.float64).reshape(
            l.shape[0], -1) for l in leaves], axis=1)
    return np.concatenate([np.asarray(l, np.float64).reshape(-1)
                           for l in leaves])


@dataclasses.dataclass
class Book:
    """The reputation record (float64 mirror of the node's book)."""

    reputation: np.ndarray
    n_tasks: np.ndarray
    good: np.ndarray             # (n, history)
    age: np.ndarray              # (n, history); inf = empty
    with_tp: np.ndarray
    total: float


def settle_task(book: Book, score, completed, rounds, dist, part,
                rp: Dict) -> Book:
    """Eq. 2-10 for one task (AutoDFL Sec. IV)."""
    nd = dist / max(dist.max(), 1e-12)
    tau = nd.mean() if rp["tau"] < 0 else rp["tau"]
    pen = np.maximum((nd - tau) / max(1.0 - tau, 1e-9), 0.0)
    o = np.clip(score * (completed / max(rounds, 1.0)) * (1 - pen), 0, 1)
    good_now = (o >= rp["r_min"]).astype(np.float64)
    age = np.where(np.isinf(book.age), np.inf, book.age + 1.0)
    age = np.concatenate([np.where(part[:, None] > 0, 0.0, np.inf),
                          age[:, :-1]], axis=1)
    good = np.concatenate([good_now[:, None], book.good[:, :-1]], axis=1)
    with_tp = book.with_tp + part
    total = book.total + part.sum()
    gm = np.where(np.isfinite(age), good, 0.0)
    c = np.exp(-np.log(2.0) * np.where(np.isfinite(age), age, 1e9)
               / rp["recency_half_life"])
    alpha = (rp["theta"] * c * gm).sum(-1)
    beta = ((1 - rp["theta"]) * c * (1 - gm)).sum(-1)
    u = 1 - np.clip(with_tp / max(total, 1.0), 0, 1)
    b = (1 - u) * alpha / np.maximum(alpha + beta, 1e-9)
    s_rep = np.clip(b + rp["sigma"] * u, 0, 1)
    l_rep = rp["gamma"] * o + (1 - rp["gamma"]) * s_rep
    n_tasks = book.n_tasks + part
    e = np.exp(-rp["lam"] * n_tasks)
    w = (1 - e) / (1 + e)
    r = np.where(l_rep >= rp["r_min"], w * book.reputation + (1 - w) * l_rep,
                 (1 - w) * book.reputation + w * l_rep)
    r = np.clip(r, 0, 1)
    keep = part[:, None] > 0
    return Book(np.where(part > 0, r, book.reputation), n_tasks,
                np.where(keep, good, book.good), np.where(keep, age, book.age),
                with_tp, total)


def payouts(reward: float, sel: np.ndarray, score: np.ndarray
            ) -> Dict[int, float]:
    s = [float(np.float32(score[i])) for i in sel]
    total = sum(x for x in s if x > 1e-6)
    return {int(i): (reward * x / total if x > 1e-6 and total > 0 else 0.0)
            for i, x in zip(sel, s)}


def emission(parts: List[List[np.ndarray]], n_select: int, batch: int,
             gas: Dict) -> Tuple[Dict[str, int], List[int], int]:
    """One epoch's Table-I counts, per-batch commit gas and settlement gas
    from each task's per-round submitter counts."""
    per_round = [[int(p.sum()) for p in task] for task in parts]
    windows = [["publishTask"] * len(parts)]
    n_rounds = len(per_round[0])
    for r in range(n_rounds):
        w = []
        for task in per_round:
            w += ["submitLocalModel"] * task[r]
            w += ["calculateObjectiveRep"] * task[r]
        if r == n_rounds - 1:
            w += ["calculateSubjectiveRep"] * (n_select * len(parts))
        windows.append(w)
    counts = {f: sum(w.count(f) for w in windows) for f in FNS}
    commit, sizes = [], []
    for w in windows:
        for lo in range(0, len(w), batch):
            seg = w[lo:lo + batch]
            sizes.append(len(seg))
            commit.append(sum(gas["commit_base"][f] + seg.count(f)
                              * gas["commit_per_call"][f] for f in FNS
                              if f in seg))
    single = len(commit) == 1 and sizes[0] <= 5
    settle = (gas["verify_single"] + gas["execute_single"] if single
              else gas["verify_multi"] + gas["execute_multi"])
    return counts, commit, settle


# -- the replay of one epoch ------------------------------------------------------
#: Eq. 2-10 constants, the paper's defaults (AutoDFL Sec. IV)
REPUTATION = {"tau": -1.0, "theta": 0.35, "sigma": 0.3, "gamma": 0.6,
              "lam": 0.35, "r_min": 0.4, "r_init": 0.5,
              "recency_half_life": 8.0}


def replay_epoch(ep: Dict, data, hp: Hyper, cycle, lazy_range,
                 val_slices: int, reward: float,
                 rp: Dict = REPUTATION) -> Tuple[Dict[str, int], Dict]:
    """Replay one recorded epoch round by round from the program's round
    inputs.  ``ep`` holds, per task, its cohort seed, its selection and
    its rounds (global in, submitters, stacked submissions, table,
    scores, merge out), the book before the epoch and the reputations and
    payouts after it.  Returns mismatch counts, and the largest error of
    each float comparison."""
    wrong = {"submissions_wrong": 0, "scores_wrong": 0, "merges_wrong": 0,
             "settlement_wrong": 0}
    # the largest error each comparison saw (how far inside its
    # tolerance the program is), and the table entries that differ
    worst = {"submission_rel": 0.0, "merge_rel": 0.0, "reputation_abs": 0.0,
             "entries_differ": 0, "close_images": 0}
    n = len(ep["book_before"].reputation)
    kind = behaviors(cycle, n)
    lazy, mal = kind == "lazy", kind == "malicious"
    vx = data.val_x.reshape((val_slices, -1) + data.val_x.shape[1:])
    vy = data.val_y.reshape(val_slices, -1)
    v_per = vy.shape[1]
    book = ep["book_before"]
    ranked = selection(book.reputation)
    with jax.default_matmul_precision("highest"):
        for task in ep["tasks"]:
            sel = np.asarray(task["sel"])
            k = len(sel)
            if not np.array_equal(sel, ranked[:k]):
                wrong["settlement_wrong"] += k
            parts = participation(task["cohort_seed"], len(task["rounds"]),
                                  sel, lazy, lazy_range)
            m = None
            completed = np.zeros(n)
            for r, rec in enumerate(task["rounds"]):
                pos = np.flatnonzero(parts[r])
                want = np.sort(sel[pos])
                got = np.asarray(rec["idxs"])
                completed[want] += 1
                if not np.array_equal(want, got):
                    wrong["submissions_wrong"] += k
                    continue
                g_in = jax.tree.map(lambda l: np.asarray(l, np.float32),
                                    rec["params_in"])
                if m is None:                    # momentum, per trainer
                    m = jax.tree.map(lambda l: np.zeros((n,) + l.shape,
                                                        np.float32), g_in)
                rows = task["rows"][r][sel]
                dp, fk = round_keys(task["cohort_seed"], r, k)
                subs, m_new, fake = _train_round(
                    hp, g_in, jax.tree.map(lambda l: l[sel], m),
                    data.train_x[rows], data.train_y[rows], dp, fk)
                keep = np.flatnonzero(parts[r] & ~mal[sel])
                for old, new in zip(jax.tree.leaves(m),
                                    jax.tree.leaves(m_new)):
                    old[sel[keep]] = np.asarray(new)[keep]
                ref = np.where(mal[sel][:, None], _flat(fake, 1),
                               _flat(subs, 1))           # selection order
                order = np.argsort(sel[pos])
                ref = ref[pos[order]]                    # submitter order
                prog = _flat(rec["stacked"], 1)
                g_flat = _flat(g_in)
                err = np.linalg.norm(prog - ref, axis=1) / np.maximum(
                    np.linalg.norm(ref - g_flat, axis=1), 1e-12)
                wrong["submissions_wrong"] += int(
                    (err > TOL["submission_rel"]).sum())
                worst["submission_rel"] = max(worst["submission_rel"],
                                              float(err.max()))
                # DON: the reference scores the program's submissions
                # (zero rows pad them to the selection: one program shape)
                right, close = _score_all(
                    jax.tree.map(lambda l: _pad(l, k).astype(np.float32),
                                 rec["stacked"]), vx, vy, TOL["logit"])
                right = np.asarray(right)[:len(got)].T
                close = np.asarray(close)[:len(got)].T
                table = np.asarray(rec["table"], np.float64)
                diff = np.abs(np.rint(table * v_per) - right)
                wrong["scores_wrong"] += int((diff > close).sum())
                worst["entries_differ"] += int((diff > 0).sum())
                worst["close_images"] += int(close.sum())
                wrong["scores_wrong"] += int(np.sum(
                    quorum(table).astype(np.float32)
                    != np.asarray(rec["scores"], np.float32)))
                # Eq. 1 given the program's scores
                want_m = _flat(merge(rec["stacked"], rec["scores"]))
                got_m = _flat(rec["params_out"])
                rel = np.linalg.norm(got_m - want_m) / max(
                    np.linalg.norm(want_m - g_flat), 1e-12)
                wrong["merges_wrong"] += int(rel > TOL["merge_rel"])
                worst["merge_rel"] = max(worst["merge_rel"], float(rel))
            # settlement inputs from the last round
            last = task["rounds"][-1]
            score = np.zeros(n)
            dist = np.zeros(n)
            idxs = np.asarray(last["idxs"])
            score[idxs] = np.asarray(last["scores"], np.float64)
            d = np.linalg.norm(_flat(last["stacked"], 1)
                               - _flat(task["final"])[None], axis=1)
            dist[idxs] = d
            missing = np.setdiff1d(sel, idxs)
            dist[missing] = d.max() if d.size and d.max() > 0 else 1.0
            part = np.zeros(n)
            part[sel] = 1.0
            book = settle_task(book, score, completed,
                               float(len(task["rounds"])), dist, part, rp)
            want_pay = payouts(reward, sel, score)
            got_pay = task["payouts"]
            wrong["settlement_wrong"] += sum(
                1 for i, v in want_pay.items()
                if abs(got_pay[i] - v) > TOL["payout_rel"] * max(abs(v), 1))
    gap = np.abs(book.reputation
                 - np.asarray(ep["reputation_after"], np.float64))
    wrong["settlement_wrong"] += int(np.sum(gap > TOL["reputation_abs"]))
    worst["reputation_abs"] = float(gap.max())
    return wrong, worst


def fig3_wrong(reputation: np.ndarray, cycle) -> int:
    """Malicious trainers not below every good one (Fig. 3)."""
    kind = behaviors(cycle, len(reputation))
    rep = np.asarray(reputation, np.float64)
    good = rep[kind == "good"]
    return int(np.sum(rep[kind == "malicious"] >= good.min()))


def compare(values: Dict[str, int]) -> List[Tuple[str, int, int]]:
    return [(k, int(values.get(k, 0)), v) for k, v in LIMITS.items()]
