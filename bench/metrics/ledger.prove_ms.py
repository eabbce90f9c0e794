"""Self ms per window of the program's ``ledger.prove`` spans: the plan
walk's prover pump, settle-session close and forced drain."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, r"^ledger\.prove$")
