"""Self ms per epoch of the program's ``fl.score`` spans: DON scoring: the oracles' score table of every submission, one dispatch over tasks x oracles x trainers scored in trainer chunks that fit the chip, read back (`core/oracle.py`)."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, r"^fl\.score$")
