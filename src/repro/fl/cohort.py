"""Trainer cohorts — the training face a TaskRuntime drives each round.

Two implementations of one interface:

  * ``AgentCohort`` wraps a list of ``TrainingAgent``s and preserves the
    legacy per-trainer Python loop exactly (object path; behaviour-rich
    small-N debugging and the equivalence baseline).
  * ``VectorCohort`` is the SoA hot path: the whole cohort trains in ONE
    vmapped dispatch per round (the ``local_steps`` scan idiom from
    fl/round.py), with behaviour profiles (malicious / lazy) applied as
    vectorized masks and DP noise drawn with per-trainer keys under one
    vmap.  This replaces the O(trainers) ``agent.train_round`` loop that
    dominated ``AutoDFL.run_task`` wall time.

Both return a ``CohortSubmissions`` whose params are STACKED (leading
trainer axis), so the DON scoring pass (core/oracle.py) and the Eq. 1
aggregation consume them without restacking.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.storage import BlobStore
from repro.fl.dp import DPConfig, privatize


@dataclasses.dataclass
class CohortSubmissions:
    """One round's submissions: sorted cohort indices + stacked params."""

    idxs: List[int]          # cohort indices that submitted, ascending
    stacked: Any             # pytree, leaves (len(idxs), ...) in idx order
    cids: Dict[int, str]     # per-idx content id of the submitted blob
    # device pytree, leaves (selection size, ...): ``stacked``'s rows then
    # zero rows, so the Eq. 1 merge and the Eq. 4 distances keep one shape
    # however many trainers skipped the round (None: pad on demand)
    padded: Any = None

    def tree_for(self, k: int):
        """Per-trainer view (k indexes ``idxs``, not the cohort)."""
        return jax.tree.map(lambda l: l[k], self.stacked)


class AgentCohort:
    """Legacy cohort: one ``TrainingAgent.train_round`` call per trainer.

    Semantics (participation RNG streams, DP keys, blob puts) are identical
    to the pre-scheduler ``AutoDFL.run_task`` loop — this path anchors the
    single-task equivalence test.
    """

    def __init__(self, agents: Sequence):
        self.agents = list(agents)
        self._opt: Dict[int, Any] = {}

    def __len__(self) -> int:
        return len(self.agents)

    def start_task(self, global_params, opt, sel_idx: Sequence[int]):
        self._opt = {i: opt.init(global_params) for i in sel_idx}

    def train(self, global_params, rnd: int,
              sel_idx: Sequence[int]) -> Optional[CohortSubmissions]:
        with obs.span("fl.train"):
            subs: Dict[int, Dict] = {}
            for i in sel_idx:
                out = self.agents[i].train_round(global_params, self._opt[i],
                                                 i, rnd)
                if out is None:
                    continue
                self._opt[i] = out["opt_state"]
                subs[i] = out
            if not subs:
                return None
            idxs = sorted(subs)
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *[subs[i]["params"] for i in idxs])
            return CohortSubmissions(idxs, stacked,
                                     {i: subs[i]["cid"] for i in idxs})


def batched_batch_fn(raw_batch_fn: Callable[[int, int], Dict],
                     local_steps: int) -> Callable:
    """Adapt a per-(client, round) batch fn to the VectorCohort signature
    ``fn(sel_idx: ndarray, rnd) -> leaves (K, H, ...)`` by host-side
    stacking.  Convenience shim — pass a natively batched fn for the zero-
    Python-loop path."""
    def fn(sel_idx: np.ndarray, rnd: int) -> Dict:
        per = [[raw_batch_fn(int(i), rnd * 1000 + s)
                for s in range(local_steps)] for i in sel_idx]
        keys = per[0][0].keys()
        return {k: jnp.stack([jnp.stack([np.asarray(b[k]) for b in row])
                              for row in per]) for k in keys}
    return fn


def pad_rows(stacked, n: int):
    """Host copy of a stacked pytree with zero rows appended up to ``n``
    rows (the merge's fixed shape when no ``padded`` copy was kept)."""
    def one(l):
        l = np.asarray(l)
        return np.concatenate(
            [l, np.zeros((n - l.shape[0],) + l.shape[1:], l.dtype)])
    return jax.tree.map(one, stacked)


def _pad_positions(sub_pos: np.ndarray, k: int):
    """``sub_pos`` padded to ``k`` gather positions (pad rows read row 0)
    and the mask of the real ones."""
    pos = np.zeros(k, np.int32)
    pos[:len(sub_pos)] = sub_pos
    return pos, np.arange(k) < len(sub_pos)


def _take_rows(leaf, pos, valid):
    """Rows ``pos`` of ``leaf``; rows where ``valid`` is False are zero."""
    g = leaf[pos]
    m = valid.reshape(valid.shape + (1,) * (g.ndim - 1))
    return jnp.where(m, g, jnp.zeros((), g.dtype))


@jax.jit
def _gather_padded(tree, pos, valid):
    return jax.tree.map(lambda l: _take_rows(l, pos, valid), tree)


def _bucket(n: int, floor: int = 1) -> int:
    """Round ``n`` up to its power-of-two bucket (the kernels/block_pack.py
    idiom) so one compiled mega program serves every nearby task count."""
    return max(floor, 1 << max(0, (int(n) - 1).bit_length()))


class CohortKernels:
    """Jitted cohort-step kernels, shared across every VectorCohort built on
    the same (model, opt, dp) — N concurrent tasks then compile ONCE (a
    per-cohort jit would recompile identical XLA programs N times)."""

    def __init__(self, model, opt, dp: DPConfig = DPConfig()):
        def local_steps_one(params, opt_state, trainer_batch):
            # the fl/round.py idiom: H sequential steps for ONE trainer,
            # lifted over the cohort by the vmap below
            def one(carry, batch):
                p, o = carry
                loss, grads = jax.value_and_grad(
                    lambda pp: model.loss(pp, batch))(p)
                p, o, _ = opt.update(grads, o, p)
                return (p, o), loss
            (params, opt_state), losses = jax.lax.scan(
                one, (params, opt_state), trainer_batch)
            return params, opt_state, jnp.mean(losses)

        def fake_one(k, params):
            return jax.tree.map(
                lambda p: (jax.random.normal(k, p.shape, jnp.float32)
                           .astype(p.dtype) * 0.1), params)

        def round_step(params, opt_state, batches, base_key, rnd,
                       mal_mask, keep_mask, use_fake):
            """The WHOLE round for a cohort as one fused program: H local
            steps per trainer (vmapped), DP on the submitted update,
            malicious-weight overwrite and opt-state keep masks — a single
            dispatch instead of ~10 eager ops per param leaf.  Per-round,
            per-trainer keys derive from (base_key, rnd) INSIDE the program
            (an eager ``random.split`` chain costs ~ms per round on CPU)."""
            n = jax.tree.leaves(opt_state)[0].shape[0]
            k_dp, k_fake = jax.random.split(
                jax.random.fold_in(base_key, rnd))
            dp_keys = jax.random.split(k_dp, n)
            fake_keys = jax.random.split(k_fake, n)
            new_p, new_o, loss = jax.vmap(
                local_steps_one, in_axes=(None, 0, 0))(params, opt_state,
                                                       batches)
            update = jax.tree.map(lambda a, b: a - b[None], new_p, params)
            noised = jax.vmap(lambda k, u: privatize(k, u, dp)[0])(
                dp_keys, update)
            submitted = jax.tree.map(lambda g, u: g[None] + u, params,
                                     noised)
            if use_fake:
                fake = jax.vmap(fake_one, in_axes=(0, None))(fake_keys,
                                                             params)
                submitted = jax.tree.map(
                    lambda f, s: jnp.where(
                        mal_mask.reshape((-1,) + (1,) * (s.ndim - 1)), f, s),
                    fake, submitted)
            new_o = jax.tree.map(
                lambda new, old: jnp.where(
                    keep_mask.reshape((-1,) + (1,) * (new.ndim - 1)), new,
                    old), new_o, opt_state)
            return submitted, new_o, loss
        self.round_step = jax.jit(round_step,
                                  static_argnames=("use_fake",))
        self._round_step_fn = round_step     # raw form for the mega vmap
        self._mega_step = None

    def mega_round_step(self):
        """``vmap(tasks) ∘ round_step`` — T whole cohort rounds as ONE
        jitted dispatch (MegaCohort).  Row t of every output is bit-exact
        equal to ``round_step`` on task t's inputs alone: the per-trainer
        programs are element-wise independent along the new task axis."""
        if self._mega_step is None:
            fn = self._round_step_fn

            def mega_round_step(params, opt_state, batches, base_keys,
                                rnds, mal_masks, keep_masks, use_fake):
                return jax.vmap(
                    lambda p, o, b, k, r, m, kp: fn(p, o, b, k, r, m, kp,
                                                    use_fake))(
                    params, opt_state, batches, base_keys, rnds,
                    mal_masks, keep_masks)
            self._mega_step = jax.jit(mega_round_step,
                                      static_argnames=("use_fake",))
        return self._mega_step


class VectorCohort:
    """Vectorized cohort: one jitted vmap(local_steps) dispatch per round.

    behaviors: per-trainer profile strings ("good" | "malicious" | "lazy"),
    matching fl/client.py semantics — malicious submits random weights
    without training, lazy skips a round with probability drawn from
    ``lazy_skip_range``.
    batch_fn(sel_idx, rnd) -> batch dict with leaves (K, H, local_B, ...)
    (H = local optimizer steps; see ``batched_batch_fn``).
    kernels: shared CohortKernels (pass one instance to all cohorts of a
    multi-task run; built on demand otherwise).
    """

    def __init__(self, model, opt, batch_fn: Callable, store: BlobStore,
                 behaviors: Optional[Sequence[str]] = None,
                 n_trainers: Optional[int] = None, local_steps: int = 4,
                 dp: DPConfig = DPConfig(),
                 lazy_skip_range=(0.4, 0.6), seed: int = 0,
                 kernels: Optional[CohortKernels] = None):
        if behaviors is None:
            assert n_trainers is not None, "need behaviors or n_trainers"
            behaviors = ["good"] * n_trainers
        self.behaviors = list(behaviors)
        self.model = model
        self.opt = opt
        self.batch_fn = batch_fn
        self.store = store
        self.local_steps = local_steps
        self.dp = dp
        self.lazy_skip_range = lazy_skip_range
        self.rng = np.random.default_rng(seed)
        self.key = jax.random.key(seed)
        self.is_lazy = np.array([b == "lazy" for b in self.behaviors])
        self.is_malicious = np.array(
            [b == "malicious" for b in self.behaviors])
        self.kernels = kernels or CohortKernels(model, opt, dp)
        self._opt = None           # stacked opt state over selected trainers
        self._opt_holder = None    # MegaCohort currently holding _opt
        self._round_counter = 0

    def __len__(self) -> int:
        return len(self.behaviors)

    def start_task(self, global_params, opt, sel_idx: Sequence[int]):
        if self._opt_holder is not None:
            self._opt_holder.flush_opt()
        k = len(sel_idx)
        o = opt.init(global_params)
        # one broadcast dispatch per leaf — jnp.stack([l] * k) built k
        # device arrays per leaf and dominated multi-task select windows
        self._opt = jax.tree.map(
            lambda l: jnp.broadcast_to(l, (k,) + l.shape), o)

    def _participation(self, sel_idx: np.ndarray) -> np.ndarray:
        lazy = self.is_lazy[sel_idx]
        r = self.rng.random(len(sel_idx))
        lo, hi = self.lazy_skip_range
        u = self.rng.uniform(lo, hi, len(sel_idx))
        return ~lazy | (r > u)

    def train(self, global_params, rnd: int,
              sel_idx: Sequence[int]) -> Optional[CohortSubmissions]:
        with obs.span("fl.train"):
            if self._opt_holder is not None:
                # a megastep holds this cohort's opt state stacked on its task
                # axis; reclaim it before stepping per-task
                self._opt_holder.flush_opt()
            sel = np.asarray(sel_idx)
            part = self._participation(sel)
            if not part.any():
                return None
            batches = self.batch_fn(sel, rnd)
            # malicious rows submit random weights without training (free-
            # riding); their opt state must not advance, nor must lazy skips'
            mal = self.is_malicious[sel]
            submitted, self._opt, _loss = self.kernels.round_step(
                global_params, self._opt, batches, self.key,
                np.uint32(self._round_counter), jnp.asarray(mal),
                jnp.asarray(part & ~mal), use_fake=bool(mal.any()))
            self._round_counter += 1
            obs.count("fl.samples", _samples(batches, part & ~mal))

            if part.all():
                sub_pos = np.argsort(sel)             # CohortSubmissions order
                stacked = (submitted if np.array_equal(sub_pos,
                                                       np.arange(len(sel)))
                           else jax.tree.map(lambda l: l[sub_pos], submitted))
            else:
                sub_pos = np.flatnonzero(part)
                sub_pos = sub_pos[np.argsort(sel[sub_pos])]
                stacked = jax.tree.map(lambda l: l[sub_pos], submitted)
            pos, valid = _pad_positions(sub_pos, len(sel))
            padded = _gather_padded(submitted, jnp.asarray(pos),
                                    jnp.asarray(valid))
            cid = self.store.put(jax.tree.map(np.asarray, stacked))
            idxs = [int(i) for i in sel[sub_pos]]
            return CohortSubmissions(idxs, stacked, {i: cid for i in idxs},
                                     padded)


def _samples(batches, trained: np.ndarray) -> int:
    """Images trained: trainers that trained x local steps x batch (the
    batch leaves are (trainers, steps, batch, ...))."""
    steps, batch = jax.tree.leaves(batches)[0].shape[1:3]
    return int(np.count_nonzero(trained)) * int(steps) * int(batch)


@functools.lru_cache(maxsize=64)
def _stack_fn(n: int):
    """Jitted n-tree stack: ONE dispatch instead of an eager per-leaf
    ``jnp.stack`` fan-out (the megastep assembles stacks every window)."""
    return jax.jit(lambda *ts: jax.tree.map(lambda *xs: jnp.stack(xs), *ts))


@functools.lru_cache(maxsize=64)
def _unstack_fn(n: int):
    """Jitted inverse: one dispatch returning n row-slices of a stacked
    tree (eager ``l[i]`` per leaf per row costs hundreds of tiny ops)."""
    return jax.jit(lambda t: tuple(
        jax.tree.map(lambda l, i=i: l[i], t) for i in range(n)))


def _stack_trees(trees):
    return _stack_fn(len(trees))(*trees)


@jax.jit
def _gather_sorted(tree, rows, pos, valid):
    """Row-select + per-row gather in ONE dispatch: leaves (B, K, ...)
    take rows ``rows`` then reorder each by its own index vector (the
    per-task ``sub_pos`` sort, padded to K; rows past a task's
    submissions are zero)."""
    return jax.tree.map(
        lambda l: jax.vmap(_take_rows)(l[rows], pos, valid), tree)


@dataclasses.dataclass
class MegaRound:
    """One megastep's outputs plus the row bookkeeping the scheduler needs
    to score/aggregate across tasks in the same stacked layout."""

    subs: List[Optional[CohortSubmissions]]  # per task (None = no cohort
                                             # member participated)
    raw: Any                  # device tree (B, K, ...), selection order —
                              # the scoring input (B = pow2 task bucket)
    sorted: Any               # device tree (B, K, ...): row a holds active
                              # task a's submissions in sub_pos order, then
                              # zero rows (None when no task is active)
    active: List[int]         # task index of raw/sorted row a
    pos: List["np.ndarray"]   # per active row: sub_pos into selection order


class MegaCohort:
    """Cross-task megastep over T same-kernel ``VectorCohort``s: stack the
    cohorts' round inputs on a leading task axis (padded to its pow2
    bucket) and advance every task with ONE ``vmap(tasks) ∘ vmap(trainers)``
    dispatch — replacing T per-task jit calls per round.

    Semantics are pinned bit-exact to stepping each ``VectorCohort.train``
    alone (tests/test_mega.py): participation draws come from each
    cohort's own rng in the same order, opt state / round counters advance
    per task, and blob cids are content-identical.  Ragged participation
    only changes the host-side gather — the kernel always trains all K
    selected trainers with per-task keep masks, exactly like the per-task
    path.
    """

    def __init__(self, cohorts: Sequence["VectorCohort"]):
        assert cohorts, "empty mega group"
        k0 = cohorts[0].kernels
        assert all(c.kernels is k0 for c in cohorts), \
            "mega group must share ONE CohortKernels (same model/opt/dp)"
        self.cohorts = list(cohorts)
        self.kernels = k0
        # opt-state residency: between consecutive megasteps over the SAME
        # row layout the stacked opt tree stays here (one (T, K, P) copy
        # each way per window otherwise).  While held, each active
        # cohort's ``_opt_holder`` points back so any per-task consumer
        # (VectorCohort.train / start_task) flushes before reading.
        self._opt_stacked = None
        self._opt_rows: Optional[List[int]] = None
        self._opt_active: Optional[List[int]] = None

    def flush_opt(self) -> None:
        """Hand the cached stacked opt state back to the cohorts (called
        before any per-task path touches ``cohort._opt``)."""
        if self._opt_stacked is None:
            return
        opts = _unstack_fn(len(self._opt_rows))(self._opt_stacked)
        for a, t in enumerate(self._opt_active):
            self.cohorts[t]._opt = opts[a]
            self.cohorts[t]._opt_holder = None
        self._opt_stacked = self._opt_rows = self._opt_active = None

    def _stacked_opt(self, rows: List[int], active: List[int]):
        if (self._opt_rows == rows
                and all(self.cohorts[t]._opt_holder is self
                        for t in active)):
            return self._opt_stacked
        self.flush_opt()
        for t in rows:
            holder = self.cohorts[t]._opt_holder
            if holder is not None and holder is not self:
                holder.flush_opt()
        return _stack_trees([self.cohorts[t]._opt for t in rows])

    def train(self, params_list: Sequence[Any], rnds: Sequence[int],
              sel_list: Sequence[Sequence[int]]) -> Optional[MegaRound]:
        with obs.span("fl.train"):
            cohorts = self.cohorts
            sels = [np.asarray(s) for s in sel_list]
            K = sels[0].size
            assert all(s.size == K for s in sels), "mega group needs uniform K"
            parts = [c._participation(s) for c, s in zip(cohorts, sels)]
            active = [t for t in range(len(cohorts)) if parts[t].any()]
            subs: List[Optional[CohortSubmissions]] = [None] * len(cohorts)
            if not active:
                return MegaRound(subs, None, None, [], [])
            # task-axis rows: active tasks padded to the pow2 bucket by
            # replicating row 0 (padded outputs are computed and dropped)
            rows = active + [active[0]] * (_bucket(len(active)) - len(active))
            batches = {t: cohorts[t].batch_fn(sels[t], rnds[t]) for t in active}
            mal = np.stack([cohorts[t].is_malicious[sels[t]] for t in rows])
            keep = np.stack([parts[t] & ~cohorts[t].is_malicious[sels[t]]
                             for t in rows])
            submitted, new_opt, _loss = self.kernels.mega_round_step()(
                _stack_trees([params_list[t] for t in rows]),
                self._stacked_opt(rows, active),
                _stack_trees([batches[t] for t in rows]),
                jnp.stack([cohorts[t].key for t in rows]),
                jnp.asarray([cohorts[t]._round_counter for t in rows],
                            jnp.uint32),
                jnp.asarray(mal), jnp.asarray(keep),
                use_fake=bool(any(mal[a].any()
                                  for a in range(len(active)))))
            obs.count("fl.samples", sum(
                _samples(batches[t], keep[a]) for a, t in enumerate(active)))
            # keep the new opt stacked here; cohorts flush it back on demand.
            # Padded rows replicate row 0's inputs, so only the active slices
            # are authoritative — flush_opt hands back exactly those
            self._opt_stacked, self._opt_rows = new_opt, rows
            self._opt_active = active
            for t in active:
                cohorts[t]._opt_holder = self
                cohorts[t]._round_counter += 1
            # per-task submitted order (the VectorCohort.train sub_pos logic),
            # padded to K so one gather serves every task, ragged or not
            pos = []
            for t in active:
                if parts[t].all():
                    pos.append(np.argsort(sels[t]))
                else:
                    p = np.flatnonzero(parts[t])
                    pos.append(p[np.argsort(sels[t][p])])
            pads = [_pad_positions(p, K) for p in pos]
            fb = list(range(len(active)))
            fb += [0] * (len(rows) - len(fb))
            sorted_all = _gather_sorted(
                submitted, jnp.asarray(fb),
                jnp.asarray(np.stack([pads[a][0] for a in fb])),
                jnp.asarray(np.stack([pads[a][1] for a in fb])))
            padded = _unstack_fn(len(fb))(sorted_all)
            # ONE host materialization; each task's blob is its leading rows
            host = jax.device_get(sorted_all)
            for a, t in enumerate(active):
                n = len(pos[a])
                stacked = jax.tree.map(lambda l, a=a, n=n: l[a, :n], host)
                cid = cohorts[t].store.put(stacked)
                idxs = [int(i) for i in sels[t][pos[a]]]
                subs[t] = CohortSubmissions(idxs, stacked,
                                            {i: cid for i in idxs}, padded[a])
            return MegaRound(subs, submitted, sorted_all, active, pos)
