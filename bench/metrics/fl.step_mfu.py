"""Share (%) of the chip's bf16 peak that the measured epochs' model
FLOPs reach over the window's wall time: images trained (``fl.samples``)
at a LeNet-5 training image's FLOPs plus images the oracles evaluated
(``fl.eval_images``) at a forward pass's (``harness.fl_costs``)."""
from harness import fl_costs


def read(run):
    peak = run.peaks.get("bf16_flops_per_s")
    counters = getattr(run.driver, "window_counters", None)
    c = counters() if counters else {}
    if not c.get("fl.samples") or not peak:
        return None
    flops = c["fl.samples"] * fl_costs.train_flops() + \
        c.get("fl.eval_images", 0) * fl_costs.forward_flops()
    return 100.0 * flops / run.window_s / peak
