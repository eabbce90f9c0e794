"""Self ms per epoch of the program's ``fl.aggregate`` spans: the Eq. 1 merges and the Eq. 4 distance pass at settlement (`fl/scheduler.py`); dispatch only, the device work is read by `fl.agg_roofline`."""
from harness.spans import self_ms


def read(run):
    return self_ms(run, r"^fl\.aggregate$")
