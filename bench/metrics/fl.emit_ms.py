"""Self ms per epoch of the program's ``fl.emit`` spans: Table-I transaction emission: `AutoDFL._tx_batch` / `_tx_batch_many` staging the round's txs into the fused window loop (`fl/server.py`)."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, r"^fl\.emit$")
