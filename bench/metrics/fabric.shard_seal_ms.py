"""Device ms per window in the fabric's lane fold (``shard_seal``: the
K lanes' segmented seal digests, ``shard_map``-ped over the chips),
summed over the chips, by its program names."""
from harness.trace import time_matching

PROGRAMS = r"lane_fold|shard_map"


def read(run):
    lo, hi = run.trace.window
    ns = sum(time_matching(ev, PROGRAMS, lo, hi)
             for ev in run.trace.modules.values())
    return ns / run.steps / 1e6 if ns and run.steps else None
