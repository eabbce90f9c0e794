"""THE shared invariant catalog for the static checker and the sanitizer.

Every rule the stack enforces lives here exactly once: the AST pass
(analysis/lint.py) and the runtime sanitizer (analysis/sanitize.py) are
two enforcement layers over this one table, so a rule id printed by
either layer resolves to the same contract, rationale and fix hint.

R001-R005 and R008 have a static form; R001 and R005-R007 have a
dynamic form (some contracts — gas conservation, receipt lifecycle —
only exist at run time, so the sanitizer carries rules the AST pass
cannot).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

#: StateArrays columns whose writes must be paired with ``mark_dirty``
#: (mirrors core.state.STATE_SCHEMA; kept literal so the linter does not
#: import numpy-heavy modules to analyze source text).
STATE_COLUMNS: Tuple[str, ...] = (
    "balances", "stake", "reputation",
    "tasks_published", "submissions", "rep_events",
)

#: kernel-registry contract: the NumPy mirror is semantics-of-record and
#: every op must carry it plus at least one device impl (R002).
REQUIRED_MIRROR_IMPL = "numpy"
MIN_IMPLS_PER_OP = 2

#: determinism sweep seeds (R003): classes whose methods anchor the
#: reachability walk, plus the free functions on the digest path.
DETERMINISM_SEED_CLASSES: Tuple[str, ...] = ("FusedWindowLoop", "StateArrays")
DETERMINISM_SEED_FUNCS: Tuple[str, ...] = (
    "canonical_bytes", "chunked_root", "chunk_fold_digests",
    "_fold_digests", "_seal_digests",
)

#: the one module allowed to mutate EventLog internals (R005).
EVENTLOG_OWNER_MODULE = "core/events.py"

#: admission-purity sweep seeds (R008): the mempool admission layer —
#: every method of these classes (and everything they reach) must
#: decide on modeled time alone, never the wall clock.
ADMISSION_SEED_CLASSES: Tuple[str, ...] = ("AdmissionController",
                                           "PendingPool")
ADMISSION_SEED_FUNCS: Tuple[str, ...] = ()


@dataclasses.dataclass(frozen=True)
class Invariant:
    """One contract: id, what it protects, and how each layer enforces it."""

    rule: str            # "R001"
    title: str
    rationale: str       # why the contract exists (one paragraph)
    fix_hint: str        # the canonical remediation, shown with findings
    static: bool         # enforced by analysis/lint.py
    dynamic: bool        # enforced by analysis/sanitize.py


CATALOG: Dict[str, Invariant] = {inv.rule: inv for inv in (
    Invariant(
        rule="R001",
        title="StateArrays writes must be paired with mark_dirty",
        rationale=(
            "The incremental dirty-chunk commitment (core/state.py) only "
            "refolds chunks covered by mark_dirty; a column write without "
            "it silently diverges the cached root from the full refold."),
        fix_hint=(
            "call state.mark_dirty(ids) after the write (same function, "
            "same id set), or route through a Tx handler that does"),
        static=True, dynamic=True,
    ),
    Invariant(
        rule="R002",
        title="kernel registry ops carry numpy mirror + a device impl + parity test",
        rationale=(
            "kernels/factory.py's contract is that the NumPy mirror is the "
            "semantics-of-record and jax/pallas/shard_map impls are pinned "
            "bit-exact against it by a tests/test_kernels.py-family test; "
            "an op missing an impl or a parity pin can drift per backend."),
        fix_hint=(
            "register a 'numpy' mirror plus at least one device impl for "
            "the op, and add a parity test mentioning the op name under "
            "tests/"),
        static=True, dynamic=False,
    ),
    Invariant(
        rule="R003",
        title="no wall-clock/RNG/id() nondeterminism on replay or digest paths",
        rationale=(
            "FusedWindowLoop replays a recorded plan and the state digest "
            "canonicalizes bytes; time.time, datetime.now, unseeded "
            "np.random and id()-keyed ordering make replay != stepped or "
            "digest != digest across processes."),
        fix_hint=(
            "thread the window clock / a seeded Generator through the call "
            "instead, and key orderings by the object (identity hash), "
            "never by id()"),
        static=True, dynamic=False,
    ),
    Invariant(
        rule="R004",
        title="jit hygiene: no host sync or traced-value branching in traced fns",
        rationale=(
            ".item()/float()/int() on traced values forces a device sync "
            "per call and Python if/while on traced values throws a "
            "ConcretizationTypeError only on the traced path; reusing a "
            "buffer donated via donate_argnums reads freed memory."),
        fix_hint=(
            "use jnp.where/lax.cond for branching, keep host pulls outside "
            "the jitted function, and never read an array after donating "
            "it"),
        static=True, dynamic=False,
    ),
    Invariant(
        rule="R005",
        title="EventLog emissions only through the owning append path",
        rationale=(
            "The log's total order (seq == position) backs cursors, fused "
            "replay equality and receipt status; mutating _events or an "
            "event's seq outside core/events.py breaks every consumer."),
        fix_hint=(
            "emit through EventLog.emit, and splice/renumber through "
            "EventLog.splice — never touch _events or seq directly"),
        static=True, dynamic=True,
    ),
    Invariant(
        rule="R008",
        title="admission decisions are pure functions of spec/sender/pool state",
        rationale=(
            "The serving layer's admission log is the determinism anchor "
            "under concurrency: replaying it must reproduce the admitted "
            "set exactly, and receipts/benchmarks compare runs by it.  A "
            "wall-clock read (time.time and friends) reachable from the "
            "admission path makes the decision depend on host scheduling "
            "instead of the modeled window clock — the one time source "
            "the ledgers run on."),
        fix_hint=(
            "pass the transaction's modeled submit time into the decision "
            "and derive every rate/refill computation from it; wall-clock "
            "timing belongs in the benchmarks, never in admission"),
        static=True, dynamic=False,
    ),
    # -- dynamic-only contracts (no useful AST form) ----------------------------
    Invariant(
        rule="R006",
        title="gas conservation: chain totals equal the sum of their parts",
        rationale=(
            "total_gas is the L1 settlement meter; if it drifts from the "
            "per-block / per-tx sums (or a rollup gas row's total from its "
            "commit+verify+execute parts) the paper's gas accounting is "
            "fiction."),
        fix_hint=(
            "only produce_block/seal may advance gas totals; never adjust "
            "total_gas or gas_log rows out of band"),
        static=False, dynamic=True,
    ),
    Invariant(
        rule="R007",
        title="receipt lifecycle legality: sealed -> proved -> aggregated",
        rationale=(
            "Receipt status is derived from the typed event stream; a "
            "ProofGenerated for a never-sealed batch or a double-proof "
            "makes client receipts lie about finality."),
        fix_hint=(
            "route batches through ProverPipeline.enqueue/pump/"
            "close_session only; never emit proof events by hand"),
        static=False, dynamic=True,
    ),
)}


def fix_hint(rule: str) -> str:
    """The catalog's canonical remediation line for ``rule`` ("" if unknown)."""
    inv = CATALOG.get(rule)
    return inv.fix_hint if inv is not None else ""
