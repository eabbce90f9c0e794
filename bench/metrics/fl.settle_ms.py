"""Self ms per epoch of the program's ``fl.settle`` spans: end-of-task settlement: the fused Eq. 2-10 update, score records, escrow payouts, `calculateSubjectiveRep` emission and the state sync (`AutoDFL.settle_window`)."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, r"^fl\.settle$")
