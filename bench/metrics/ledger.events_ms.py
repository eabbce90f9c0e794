"""Self ms per window of the program's ``ledger.events`` spans: building
the window's ``BlockPacked`` events and splicing them into the event log
(``EventLog.splice``)."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, r"^ledger\.events$")
