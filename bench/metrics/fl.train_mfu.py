"""Share (%) of the chip's bf16 peak that local training reaches on the
device: the measured epochs' training FLOPs (``fl.samples`` images at a
LeNet-5 training image's FLOPs, ``harness.fl_costs``) over the device time
of the training programs (``fl_costs.PROGRAMS["train"]``) in the window.
Trainers a round drops (malicious, skipping) are computed by the program
and not counted."""
from harness import fl_costs


def read(run):
    peak = run.peaks.get("bf16_flops_per_s")
    counters = getattr(run.driver, "window_counters", None)
    c = counters() if counters else {}
    s = fl_costs.program_seconds(run.trace, fl_costs.PROGRAMS["train"])
    if not c.get("fl.samples") or not s or not peak:
        return None
    flops = c["fl.samples"] * fl_costs.train_flops()
    return 100.0 * flops / s / peak
