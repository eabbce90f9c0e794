"""Jit'd public wrappers over the Pallas kernels.

The kernels run compiled on a TPU backend and in interpret mode
everywhere else; nothing overrides that choice.  The XLA reference paths
(ref.py) remain the numerics oracle and the dry-run/roofline path
(custom-calls hide FLOPs from cost analysis).
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gmm import gmm as _gmm
from repro.kernels.model_distance import model_distance as _dist
from repro.kernels.rollup_digest import rollup_digest as _digest
from repro.kernels.slstm_scan import slstm_scan as _slstm
from repro.kernels.weighted_agg import weighted_agg as _wagg


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def weighted_agg(stacked, scores, **kw):
    return _wagg(stacked, scores, interpret=_interpret(), **kw)


def model_distance(local, global_, **kw):
    return _dist(local, global_, interpret=_interpret(), **kw)


def flash_attention(q, k, v, causal=True, **kw):
    return _flash(q, k, v, causal=causal, interpret=_interpret(), **kw)


def gmm(xe, w, **kw):
    return _gmm(xe, w, interpret=_interpret(), **kw)


def rollup_digest(buf, **kw):
    return _digest(buf, interpret=_interpret(), **kw)


def slstm_scan(wx, r_expanded, h0, c0, n0, m0, nh, **kw):
    return _slstm(wx, r_expanded, h0, c0, n0, m0, nh,
                  interpret=_interpret(), **kw)


# re-export oracles for tests
weighted_agg_ref = ref.weighted_agg_ref
model_distance_ref = ref.model_distance_ref
flash_attention_ref = ref.flash_attention_ref
gmm_ref = ref.gmm_ref
rollup_digest_ref = ref.rollup_digest_ref
