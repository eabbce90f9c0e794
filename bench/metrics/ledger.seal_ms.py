"""Self ms per window of the program's ``ledger.seal`` spans: the seal
precompute over the window's batches (``_prepare_seals``) and each
seal's application (``_apply_seal``; on a fabric the lanes and
``_finish_window``), less the state commitment and kernels inside."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, r"^ledger\.seal$")
