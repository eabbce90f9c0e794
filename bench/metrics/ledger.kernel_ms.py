"""Device ms per window in the ledger kernels, summed over the chips:
every device program that runs in the measured window.  The window
loop's only device work is its kernels (``batch_seal``, ``dirty_fold``
with its row gather, ``block_pack``, ``shard_seal`` on a fabric), so a
kernel that moves part of its work into another program, or fuses two,
stays counted."""
from harness.trace import clip


def read(run):
    lo, hi = run.trace.window
    ns = sum(d for ev in run.trace.modules.values()
             for _, _, d in clip(ev, lo, hi))
    return ns / run.steps / 1e6 if ns and run.steps else None
