"""Share (%) of the chip's bf16 peak that DON scoring reaches on the
device: the measured epochs' evaluation FLOPs (``fl.eval_images`` images
at a LeNet-5 forward pass's FLOPs, ``harness.fl_costs``) over the device
time of the scoring programs (``fl_costs.PROGRAMS["score"]``).  Rows of
trainers that did not submit are scored by the program and not
counted."""
from harness import fl_costs


def read(run):
    peak = run.peaks.get("bf16_flops_per_s")
    counters = getattr(run.driver, "window_counters", None)
    c = counters() if counters else {}
    s = fl_costs.program_seconds(run.trace, fl_costs.PROGRAMS["score"])
    if not c.get("fl.eval_images") or not s or not peak:
        return None
    flops = c["fl.eval_images"] * fl_costs.forward_flops()
    return 100.0 * flops / s / peak
