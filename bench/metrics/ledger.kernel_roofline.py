"""Share (%) of the HBM roofline the ledger kernels reach: the bytes
their calls in the window need (``harness.costs``, from the window
sizes) moved at the chip's HBM bandwidth, over the device time of every
program in the window (all of it is the kernels' work, see
``ledger.kernel_ms``).  The kernels do no arithmetic worth a compute
bound (u32 xor-mix folds and binary searches), so bandwidth is the bound
that holds."""
from harness.trace import clip


def read(run):
    lo, hi = run.trace.window
    ns = sum(d for ev in run.trace.modules.values()
             for _, _, d in clip(ev, lo, hi))
    if not ns:
        return None
    need_s = run.driver.kernel_bytes() / run.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / (ns / 1e9)
