"""Idle share (%) of the chip over the measured window of an FL cell:
1 - (union of device program intervals / window), averaged over the
chips."""
from harness.trace import device_busy_s


def read(run):
    if not run.trace.modules:
        return None
    return 100.0 * (1.0 - device_busy_s(run.trace)
                    / (run.trace.window_ns / 1e9))
