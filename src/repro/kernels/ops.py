"""Jit'd public wrappers over the Pallas kernels.

The kernels run compiled on a TPU backend and in interpret mode
everywhere else, as the kernel factory's one backend probe
(``factory.on_tpu``) answers; nothing overrides that choice.  The XLA
reference paths (ref.py) remain the numerics oracle and the
dry-run/roofline path (custom-calls hide FLOPs from cost analysis).
"""
from __future__ import annotations

from repro.kernels import ref
from repro.kernels.factory import on_tpu
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gmm import gmm as _gmm
from repro.kernels.rollup_digest import rollup_digest as _digest
from repro.kernels.slstm_scan import slstm_scan as _slstm


def _interpret() -> bool:
    return not on_tpu()


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
flash_attention_ref = ref.flash_attention_ref
gmm_ref = ref.gmm_ref
rollup_digest_ref = ref.rollup_digest_ref
