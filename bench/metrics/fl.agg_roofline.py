"""Share (%) of the HBM roofline the Eq. 1 merges and the Eq. 4 distance
passes reach: the bytes the measured epochs' merges and distances need
(every submission read once, ``harness.fl_costs``) at the chip's HBM
bandwidth, over the device time of those programs
(``fl_costs.PROGRAMS["aggregate"]``).  Both are bound by reading the
submissions, so bandwidth is the bound that holds."""
from harness import fl_costs


def read(run):
    peak = run.peaks.get("hbm_bytes_per_s")
    agg_bytes = getattr(run.driver, "agg_bytes", None)
    s = fl_costs.program_seconds(run.trace, fl_costs.PROGRAMS["aggregate"])
    if agg_bytes is None or not s or not peak:
        return None
    return 100.0 * agg_bytes() / peak / s
