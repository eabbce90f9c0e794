"""Typed node-construction specs — the ONE public way to describe a node.

The reproduction grew five ledger backends (``Chain``/``Rollup`` on the
object path, ``VectorChain``/``VectorRollup`` on the SoA path, plus the
``ShardedRollup`` fabric) selected through scattered string flags
(``engine="object"``, ``use_rollup=``, ``n_shards=``, ``shard_route=``)
and a 13-kwarg ``AutoDFL.__init__``.  This module replaces that wiring
with small frozen dataclasses, composed into a ``NodeSpec``:

  * ``ChainSpec``       — the L1 (QBFT parameters + which engine path)
  * ``RollupSpec``      — the L2 sequencer (batch size, lanes, timing)
  * ``ProverSpec``      — the proof pipeline (aggregation width, prover
    capacity/latency, eager vs. windowed finalization)
  * ``ShardSpec``       — the sharded fabric (shard count, routing)
  * ``ReputationSpec``  — paper Eq. 2-10 constants
  * ``DONSpec``         — decentralized-oracle-network quorum config
  * ``WorkloadSpec``    — a core/workloads.py scenario, as data
  * ``FLTaskSpec``      — one FL task's lifecycle parameters

Specs are *data*: frozen, comparable, serializable (``asdict``) — a
benchmark or example declares its scenario as a spec and hands it to
``repro.api.build_ledger`` / ``AutoDFL(..., spec=...)`` instead of
hand-wiring constructors.  ``repro/api/presets.py`` catalogs the specs
the benchmarks run.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

from repro.core.gas import DEFAULT_GAS, ROLLUP_BATCH, GasTable
from repro.core.interconnect import InterconnectSpec
from repro.core.oracle import DONConfig
from repro.core.reputation import ReputationParams

#: engine paths a ChainSpec can select (the old ``engine=`` string flag)
CHAIN_BACKENDS = ("vector", "object")


@dataclasses.dataclass(frozen=True)
class ChainSpec:
    """L1 permissioned chain: QBFT quorum + gas-limited FIFO blocks.

    ``backend="vector"`` is the SoA hot path (core/engine.VectorChain);
    ``"object"`` the per-Tx simulator (core/ledger.Chain) for
    handler-rich small-N debugging.  Both are bit-identical in
    semantics (tests/test_engine.py).
    """

    backend: str = "vector"
    n_validators: int = 4
    block_time: float = 1.0
    block_gas_limit: int = 9_000_000
    gas_table: GasTable = DEFAULT_GAS

    def __post_init__(self):
        if self.backend not in CHAIN_BACKENDS:
            raise ValueError(f"unknown chain backend {self.backend!r}; "
                             f"choose from {CHAIN_BACKENDS}")


@dataclasses.dataclass(frozen=True)
class RollupSpec:
    """L2 zk-rollup sequencer (paper §III-C.3).

    Presence of a RollupSpec in a NodeSpec IS the old ``use_rollup=True``;
    ``NodeSpec(rollup=None)`` is the single-layer L1 baseline.
    """

    batch_size: int = ROLLUP_BATCH
    n_lanes: int = 1
    prove_time: float = 0.9
    per_tx_time: float = 0.14

    def __post_init__(self):
        if self.n_lanes < 1:
            raise ValueError("n_lanes must be >= 1")


@dataclasses.dataclass(frozen=True)
class ProverSpec:
    """Proof pipeline (core/prover.py): how sealed batches become one
    verified L1 posting.

    ``agg_width``: settle-sessions folded into one aggregate proof — the
    single L1 verify amortizes across every batch of the aggregate (the
    paper's 20X gas lever, tunable).  Width 1 posts at every
    ``settle_session`` — bit-equivalent to the pre-pipeline settlement
    path (pinned by tests/test_prover.py).

    ``capacity``/``prove_time``: the modeled prover — ``capacity``
    concurrent workers, ``prove_time`` seconds per batch proof
    (``None`` inherits ``RollupSpec.prove_time``).  Jobs drain on the
    shared window clock (``pump``/``NodeClient.run_until``).

    ``finalize``: ``"eager"`` posts as soon as ``agg_width`` sessions
    close; ``"window"`` defers posting to window-clock pumps, releasing
    only aggregates whose proofs have fully drained (``flush`` always
    forces the remainder).
    """

    agg_width: int = 1
    capacity: int = 1
    prove_time: Optional[float] = None
    finalize: str = "eager"             # "eager" | "window"

    def __post_init__(self):
        from repro.core.prover import FINALIZE_MODES
        if self.agg_width < 1:
            raise ValueError("agg_width must be >= 1")
        if self.capacity < 1:
            raise ValueError("prover capacity must be >= 1")
        if self.finalize not in FINALIZE_MODES:
            raise ValueError(f"unknown finalize mode {self.finalize!r}; "
                             f"choose from {FINALIZE_MODES}")


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Sharded rollup fabric (core/shards.py): K sequencers, one L1.

    ``count=1`` without ``fabric=True`` means a plain (unsharded) rollup;
    ``fabric=True`` forces the ``ShardedRollup`` wrapper even at one
    shard — bit-equivalent to ``VectorRollup`` (pinned by tests) but with
    fabric roots and per-shard receipts.

    ``mesh`` governs whether the fused window loop folds the K shard
    lanes' seal digests through the mesh-mapped ``shard_seal`` kernel
    (kernels/shard_lanes.py over launch/mesh.make_shard_mesh): ``"auto"``
    uses the device mesh exactly when more than one local device exists,
    ``"on"``/``"off"`` force it.  A pure performance choice — every impl
    is bit-exact (pinned by tests/test_shard_lanes.py).

    ``interconnect`` (core/interconnect.InterconnectSpec) overrides the
    fabric's modeled per-link wire costs — shard->L1 root gathering,
    shard<->shard settlement scatter, cohort->shard submission.  ``None``
    means the default single-datacenter links; the model only feeds the
    benchmark latency decomposition, never the Table-II numbers.
    """

    count: int = 1
    route: str = "hash"                 # "hash" | "least_loaded"
    fabric: bool = False
    mesh: str = "auto"                  # "auto" | "on" | "off"
    interconnect: Optional[InterconnectSpec] = None

    def __post_init__(self):
        if self.count < 1:
            raise ValueError("shard count must be >= 1")
        if self.route not in ("hash", "least_loaded"):
            raise ValueError(f"unknown shard route {self.route!r}")
        if self.mesh not in ("auto", "on", "off"):
            raise ValueError(f"unknown shard mesh mode {self.mesh!r}; "
                             "choose from ('auto', 'on', 'off')")

    @property
    def wants_fabric(self) -> bool:
        return self.fabric or self.count > 1


@dataclasses.dataclass(frozen=True)
class ReputationSpec(ReputationParams):
    """Paper Eq. 2-10 constants, as a spec (field docs on
    core/reputation.ReputationParams)."""

    def to_params(self) -> ReputationParams:
        return ReputationParams(**dataclasses.asdict(self))

    @classmethod
    def from_params(cls, p: ReputationParams) -> "ReputationSpec":
        return cls(**dataclasses.asdict(p))


@dataclasses.dataclass(frozen=True)
class DONSpec(DONConfig):
    """Decentralized oracle network quorum config (core/oracle.DONConfig)."""

    def to_config(self) -> DONConfig:
        return DONConfig(**dataclasses.asdict(self))

    @classmethod
    def from_config(cls, c: DONConfig) -> "DONSpec":
        return cls(**dataclasses.asdict(c))


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """A core/workloads.py scenario, as data.

    ``options`` are the scenario factory's extra kwargs, stored as a
    sorted item tuple so the spec stays hashable/frozen.
    """

    scenario: str = "poisson"
    rate: float = 100.0
    duration: float = 30.0
    seed: int = 0
    options: Tuple[Tuple[str, Any], ...] = ()

    @classmethod
    def make(cls, scenario: str, rate: float, duration: float = 30.0,
             seed: int = 0, **options) -> "WorkloadSpec":
        return cls(scenario, rate, duration, seed,
                   tuple(sorted(options.items())))

    def build(self):
        """Materialize the Workload (time-sorted TxArrays + metadata)."""
        from repro.core.workloads import make_workload
        return make_workload(self.scenario, self.rate,
                             duration=self.duration, seed=self.seed,
                             **dict(self.options))


@dataclasses.dataclass(frozen=True)
class FLTaskSpec:
    """One FL task's lifecycle parameters (paper Fig. 1 steps 1-16).

    Consumed by ``AutoDFL.run_task`` and ``Scheduler.add_task`` in place
    of their loose kwargs.
    """

    task_id: str
    rounds: int = 5
    reward: float = 10.0
    n_select: Optional[int] = None
    start_window: int = 0
    init_seed: int = 0


def as_task_spec(task, **kw) -> FLTaskSpec:
    """Back-compat shim shared by ``AutoDFL.run_task`` and
    ``Scheduler.add_task``: a task-id string plus loose kwargs becomes an
    FLTaskSpec (defaults live on FLTaskSpec alone); an FLTaskSpec passes
    through, rejecting extra kwargs it would otherwise shadow."""
    if isinstance(task, str):
        return FLTaskSpec(task, **{k: v for k, v in kw.items()
                                   if v is not None})
    if not isinstance(task, FLTaskSpec):
        raise TypeError(f"expected task id or FLTaskSpec, got {task!r}")
    extra = {k for k, v in kw.items() if v is not None}
    if extra:
        raise ValueError(f"kwargs {sorted(extra)} conflict with the "
                         f"FLTaskSpec; set them on the spec")
    return task


@dataclasses.dataclass(frozen=True)
class NodeSpec:
    """The full node: L1 + optional L2 (+ optional fabric) + protocol
    constants.  ``build_ledger(spec)`` turns the ledger part into a
    LedgerBackend; ``AutoDFL(..., spec=spec)`` builds the protocol node.

    ``n_trainers=None`` defers the cohort size to the caller
    (``AutoDFL``'s positional argument); ``build_node`` requires it.
    """

    chain: ChainSpec = dataclasses.field(default_factory=ChainSpec)
    rollup: Optional[RollupSpec] = dataclasses.field(
        default_factory=RollupSpec)
    prover: Optional[ProverSpec] = None     # None = default proof pipeline
    shards: Optional[ShardSpec] = None
    reputation: ReputationSpec = dataclasses.field(
        default_factory=ReputationSpec)
    don: DONSpec = dataclasses.field(default_factory=DONSpec)
    n_trainers: Optional[int] = None
    trainer_funds: float = 10.0
    publisher_funds: float = 1000.0
    seed: int = 0
    workload: Optional[WorkloadSpec] = None     # background traffic
    tasks: Tuple[FLTaskSpec, ...] = ()          # declarative task set

    def __post_init__(self):
        if self.prover is not None and self.rollup is None:
            raise ValueError("a ProverSpec needs a RollupSpec (the proof "
                             "pipeline settles sealed L2 batches)")
        if self.shards is not None and self.shards.wants_fabric:
            if self.rollup is None:
                raise ValueError("a sharded fabric needs a RollupSpec")
            if self.chain.backend != "vector":
                raise ValueError("sharding needs the vector chain backend")
        if self.rollup is not None and self.chain.backend == "object":
            # the object Rollup has no lane striping — reject rather than
            # silently build a single-lane rollup
            if self.rollup.n_lanes != 1:
                raise ValueError("n_lanes > 1 needs the vector backend")

    # -- legacy flag mapping (the deprecation shim's single source) --------
    @classmethod
    def from_legacy(cls, *, engine: str = "object", use_rollup: bool = True,
                    n_shards: int = 1, shard_route: str = "hash",
                    rep_params: Optional[ReputationParams] = None,
                    don: Optional[DONConfig] = None,
                    trainer_funds: float = 10.0,
                    publisher_funds: float = 1000.0,
                    seed: int = 0) -> "NodeSpec":
        """Map the old AutoDFL kwargs onto a NodeSpec (one release shim).

        The mapping is pinned against the legacy constructor path by
        tests/test_api.py: same state root, same gas totals.
        """
        shards = (ShardSpec(count=n_shards, route=shard_route)
                  if n_shards > 1 else None)
        return cls(
            chain=ChainSpec(backend=engine),
            rollup=RollupSpec() if use_rollup else None,
            shards=shards,
            reputation=(ReputationSpec.from_params(rep_params)
                        if rep_params is not None else ReputationSpec()),
            don=(DONSpec.from_config(don) if don is not None else DONSpec()),
            trainer_funds=trainer_funds, publisher_funds=publisher_funds,
            seed=seed)

    def describe(self) -> Dict[str, Any]:
        """JSON-friendly summary (used by benchmarks/run.py --all)."""
        d = dataclasses.asdict(self)
        d["chain"].pop("gas_table", None)       # calibration table, not data
        return d


#: reputation-gate policies an AdmissionSpec can select
REP_GATES = ("off", "surcharge", "reject")


@dataclasses.dataclass(frozen=True)
class AdmissionSpec:
    """Mempool admission rules for the node service (repro/serve).

    Every rule is a pure function of (this spec, the sender's modeled
    state, the pending pool) — no wall clock anywhere on the decision
    path (rule R008); the token bucket refills on the MODELED submit
    time, the same window clock the ledgers run on.

      * ``rate_limit``/``burst`` — per-sender token bucket: ``burst``
        tokens deep, refilling ``rate_limit`` tokens per modeled second;
        each transaction consumes one token.
      * ``fee_floor`` — minimum offered fee (gas) for any transaction.
      * ``rep_gate`` — senders whose reputation is below the trust line
        (``ReputationParams.r_min``; unknown senders start at ``r_init``)
        are ``"reject"``-ed outright, or under ``"surcharge"`` must
        offer at least ``rep_surcharge`` x the function's intrinsic gas;
        ``"off"`` disables the gate.
      * ``pool_cap``/``evict`` — the pending pool holds at most
        ``pool_cap`` admitted transactions per flush window; at cap,
        ``evict=True`` drops the lowest-fee entry to make room for a
        strictly higher-fee arrival (spam eviction — spam floods the
        cheapest function, so it drains first), ``evict=False`` rejects
        the arrival as overloaded instead.
    """

    rate_limit: float = 50.0
    burst: float = 20.0
    fee_floor: int = 0
    rep_gate: str = "surcharge"
    rep_surcharge: float = 1.5
    pool_cap: int = 4096
    evict: bool = True

    def __post_init__(self):
        if self.rate_limit <= 0 or self.burst < 1:
            raise ValueError("rate_limit must be > 0 and burst >= 1")
        if self.rep_gate not in REP_GATES:
            raise ValueError(f"unknown rep_gate {self.rep_gate!r}; "
                             f"choose from {REP_GATES}")
        if self.rep_surcharge < 1.0:
            raise ValueError("rep_surcharge must be >= 1.0")
        if self.pool_cap < 1:
            raise ValueError("pool_cap must be >= 1")


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    """One concurrent node service (repro/serve.NodeService): the node it
    fronts, its admission rules, and the serving knobs.

      * ``queue_cap`` — bound of the single-writer op queue; a submit
        arriving while the queue is full gets an explicit
        ``overloaded``/HTTP-429 response (the backpressure contract).
      * ``window`` — modeled seconds between pool flushes: the service
        drains the admitted pool into the ledger, seals, and pumps
        ``run_until`` at every window boundary the modeled clock
        crosses.
      * ``event_cap`` — bounds the stack's EventLog as a ring buffer so
        long-lived multi-consumer serving cannot grow it without limit
        (``None`` keeps the default unbounded log).
    """

    node: NodeSpec = dataclasses.field(default_factory=NodeSpec)
    admission: AdmissionSpec = dataclasses.field(
        default_factory=AdmissionSpec)
    host: str = "127.0.0.1"
    port: int = 8545
    queue_cap: int = 1024
    window: float = 1.0
    event_cap: Optional[int] = None

    def __post_init__(self):
        if self.queue_cap < 1:
            raise ValueError("queue_cap must be >= 1")
        if self.window <= 0:
            raise ValueError("window must be > 0 modeled seconds")
        if self.event_cap is not None and self.event_cap < 1:
            raise ValueError("event_cap must be >= 1 (or None)")
