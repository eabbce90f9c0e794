"""Mean wall ms per window of ``FusedWindowLoop.execute()`` (seal
precompute and digests, prover, L1 block packing, state commitment):
the benchmark's ``ledger.execute`` host span."""
from harness.trace import clip


def read(run):
    lo, hi = run.trace.window
    d = [s[2] for s in clip(run.trace.spans, lo, hi)
         if s[0] == "ledger.execute"]
    return sum(d) / len(d) / 1e6 if d else None
