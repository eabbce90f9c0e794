"""Mean wall ms per window of the fused loop's record phase (submit,
flush, pump and run_until into a fresh ``FusedWindowLoop`` plan): the
benchmark's ``ledger.record`` host span."""
from harness.trace import clip


def read(run):
    lo, hi = run.trace.window
    d = [s[2] for s in clip(run.trace.spans, lo, hi) if s[0] == "ledger.record"]
    return sum(d) / len(d) / 1e6 if d else None
