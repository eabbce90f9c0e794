"""Events copied by ``EventLog.splice`` per window (the ``events.moved``
counter over ``windows``).  A mean over every window the process
executed, the warm-up windows included."""
from harness.spans import counter_per_window


def read(run):
    return counter_per_window("events.moved")
