"""Host ms per window inside the kernel factory's device impls: the self
time of every ``ledger.kernel.<op>`` span (staging the NumPy inputs,
dispatch, waiting for the device, reading the result back)."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, r"^ledger\.kernel\.")
