"""One ledger factory: ``build_ledger(spec) -> LedgerBackend``.

Every backend the repo grew — ``Chain``/``Rollup`` (object path),
``VectorChain``/``VectorRollup`` (SoA path), ``ShardedRollup`` (fabric) —
is constructed here from a typed spec instead of string flags scattered
over call sites.  The factory is the only place that knows which class
each spec combination maps to:

    ChainSpec alone (or NodeSpec(rollup=None))   -> VectorChain | Chain
    + RollupSpec                                 -> VectorRollup | Rollup
    + ShardSpec(count>1 or fabric=True)          -> ShardedRollup

``build_ledger`` returns the SUBMISSION target (the L2 face when a
rollup is configured, else the L1 itself); the rollup faces keep their
L1 on ``.l1``, and ``l1_of`` resolves it uniformly.
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

from repro.api.specs import ChainSpec, NodeSpec
from repro.core.ledger import LedgerBackend

LedgerSpec = Union[NodeSpec, ChainSpec]


def _as_node_spec(spec: LedgerSpec) -> NodeSpec:
    if isinstance(spec, ChainSpec):
        return NodeSpec(chain=spec, rollup=None)
    if isinstance(spec, NodeSpec):
        return spec
    raise TypeError(f"expected NodeSpec or ChainSpec, got {type(spec)!r}")


def build_chain(spec: ChainSpec, *, fns=None):
    """Build just the L1 from a ChainSpec.

    ``fns``: optional engine FnRegistry to share (vector backend only) —
    a runtime handle, deliberately NOT part of the spec data.
    """
    if spec.backend == "vector":
        from repro.core.engine import VectorChain
        return VectorChain(n_validators=spec.n_validators,
                           block_time=spec.block_time,
                           block_gas_limit=spec.block_gas_limit,
                           gas_table=spec.gas_table, fns=fns)
    from repro.core.ledger import Chain
    return Chain(n_validators=spec.n_validators, block_time=spec.block_time,
                 block_gas_limit=spec.block_gas_limit,
                 gas_table=spec.gas_table)


def build_stack(spec: LedgerSpec, *, fns=None, state=None
                ) -> Tuple[object, Optional[object]]:
    """Build (l1_chain, rollup_or_None) from a spec.

    ``state``: optional pre-built StateArrays for the sharded fabric.
    """
    from repro.api.specs import ProverSpec
    node = _as_node_spec(spec)
    chain = build_chain(node.chain, fns=fns)
    ru = node.rollup
    if ru is None:
        return _sanitized(chain, None)
    pv = node.prover if node.prover is not None else ProverSpec()
    prove_time = ru.prove_time if pv.prove_time is None else pv.prove_time
    prover_kw = dict(agg_width=pv.agg_width, prover_capacity=pv.capacity,
                     finalize=pv.finalize)
    if node.shards is not None and node.shards.wants_fabric:
        from repro.core.shards import ShardedRollup
        return _sanitized(chain, ShardedRollup(
            chain, n_shards=node.shards.count, batch_size=ru.batch_size,
            gas_table=node.chain.gas_table, prove_time=prove_time,
            per_tx_time=ru.per_tx_time, n_lanes=ru.n_lanes,
            route=node.shards.route,
            state=state, interconnect=node.shards.interconnect,
            mesh=node.shards.mesh, **prover_kw))
    if node.chain.backend == "vector":
        from repro.core.engine import VectorRollup
        return _sanitized(chain, VectorRollup(
            chain, batch_size=ru.batch_size, gas_table=node.chain.gas_table,
            prove_time=prove_time, per_tx_time=ru.per_tx_time,
            n_lanes=ru.n_lanes, **prover_kw))
    from repro.core.rollup import Rollup
    return _sanitized(chain, Rollup(chain, batch_size=ru.batch_size,
                                    gas_table=node.chain.gas_table,
                                    prove_time=prove_time,
                                    per_tx_time=ru.per_tx_time, **prover_kw))


def _sanitized(chain, rollup):
    """REPRO_SANITIZE=1 installs the runtime sanitizer on every stack
    this factory builds (see analysis/sanitize.py; a no-op otherwise)."""
    from repro.analysis import sanitize
    if sanitize.enabled():
        sanitize.install_stack(chain, rollup)
    return chain, rollup


def build_ledger(spec: LedgerSpec, *, fns=None, state=None) -> LedgerBackend:
    """THE ledger factory: spec -> the LedgerBackend you submit to.

    When the spec configures a rollup, the returned backend is the L2
    face and its L1 is reachable as ``.l1``; otherwise the L1 itself is
    returned.  Use ``l1_of`` to resolve the chain either way.
    """
    chain, rollup = build_stack(spec, fns=fns, state=state)
    return rollup if rollup is not None else chain


def l1_of(backend) -> object:
    """The L1 chain behind any backend built by ``build_ledger``."""
    return getattr(backend, "l1", backend)


def build_node(spec: NodeSpec, model, opt, eval_fn, val_batch, **kw):
    """Build a full protocol node (fl/server.AutoDFL) from a NodeSpec.

    ``spec.n_trainers`` is required here (the ledger-only factories
    don't need it).  Extra ``kw`` are forwarded to AutoDFL.
    """
    if spec.n_trainers is None:
        raise ValueError("build_node needs spec.n_trainers")
    from repro.fl.server import AutoDFL
    return AutoDFL(model, opt, spec.n_trainers, eval_fn, val_batch,
                   spec=spec, **kw)
