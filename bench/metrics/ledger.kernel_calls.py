"""Device kernel calls per window: ``ledger.kernel.<op>`` spans in the
measured window over its windows."""
from harness.spans import spans_per_window


def read(run):
    return spans_per_window(run, r"^ledger\.kernel\.")
