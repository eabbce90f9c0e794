"""Self ms per window of the program's ``ledger.pack`` spans: L1 block
packing up to the event splice (confirm times, ``BlockStats``, handler
dispatch), less the ``block_pack`` kernel call inside."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, r"^ledger\.pack$")
