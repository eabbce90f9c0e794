"""Self ms per window of the program's ``ledger.commit`` spans
(``StateArrays.root`` / ``partition_roots``): patching the dirty rows
into the cached word buffer, the dirty-chunk list and the sha256 seal,
less the ``dirty_fold`` kernel call inside."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, r"^ledger\.commit$")
