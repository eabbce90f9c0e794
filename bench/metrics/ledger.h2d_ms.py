"""Host ms per window the JAX runtime spends staging host-to-device
copies (its linearize, H2D dispatch and transfer-to-device spans, a
union over host threads): every kernel-factory impl takes and returns
NumPy.  These are the runtime's host spans, not the device's DMA."""
from harness.trace import busy_ns


def read(run):
    lo, hi = run.trace.window
    if not run.trace.h2d or not run.steps:
        return None
    return busy_ns(run.trace.h2d, lo, hi) / run.steps / 1e6
