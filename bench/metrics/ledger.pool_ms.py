"""Self ms per window of the program's ``ledger.pool`` spans: staging
L1 txs into the mempool (``VectorChain.submit_arrays`` from the plan
walk) and consolidating it before the packer (``_consolidate``)."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, r"^ledger\.pool$")
