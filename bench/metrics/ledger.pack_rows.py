"""Mempool rows handed to the L1 block packer per window (the
``pack.rows`` counter over ``windows``): the history the packer reads,
which grows over a run.  A mean over every window the process executed,
the warm-up windows included."""
from harness.spans import counter_per_window


def read(run):
    return counter_per_window("pack.rows")
