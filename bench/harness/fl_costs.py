"""Work of the FL programs, from the layer shapes: the yardstick for the
FL cells' MFU and roofline shares.

LeNet-5 at its published widths (LeCun et al. 1998), 32x32x1 input:
multiply-adds per image of each layer (pooling and activations are not
counted), two FLOPs each; a training image costs its forward pass and a
backward pass of twice the forward's FLOPs.  The Eq. 1 merge and the Eq. 4
distances are bound by reading the stacked submissions: their bytes are
the submissions read once (f32) and the result written once.

``PROGRAMS`` names the jitted programs of each FL layer as they show in
the device trace (``jit_<function name>``), in one place.
"""
from __future__ import annotations

F32 = 4

#: multiply-adds per image, layer by layer
LENET_MACS = {
    "conv1": 28 * 28 * 6 * 5 * 5 * 1,
    "conv2": 10 * 10 * 16 * 5 * 5 * 6,
    "fc1": 400 * 120,
    "fc2": 120 * 84,
    "fc3": 84 * 10,
}
LENET_PARAMS = (5 * 5 * 1 * 6 + 6) + (5 * 5 * 6 * 16 + 16) + \
    (400 * 120 + 120) + (120 * 84 + 84) + (84 * 10 + 10)

#: the device programs of each FL layer (XLA module names)
PROGRAMS = {
    "train": ("jit_mega_round_step", "jit_round_step"),
    "score": ("jit_mega_score",),
    "aggregate": ("jit_weighted_average_tree_mega",
                  "jit_weighted_average_tree_jit", "jit__settle_distances"),
}


def forward_flops() -> int:
    """FLOPs of one image's forward pass."""
    return 2 * sum(LENET_MACS.values())


def train_flops() -> int:
    """FLOPs of one training image: forward, and backward at twice it."""
    return 3 * forward_flops()


def merge_bytes(n_submissions: int, params: int = LENET_PARAMS) -> int:
    """One Eq. 1 merge: every submission read, the merged model written
    (the scores are negligible)."""
    return (n_submissions + 1) * params * F32


def distance_bytes(n_submissions: int, params: int = LENET_PARAMS) -> int:
    """One Eq. 4 pass: the submissions and the global model read, one
    distance per submission written."""
    return ((n_submissions + 1) * params + n_submissions) * F32


def program_seconds(trace, names) -> float:
    """Device seconds, summed over the chips, of the programs ``names``
    inside the measured window."""
    from harness.trace import clip, module_name
    lo, hi = trace.window
    return sum(d for ev in trace.modules.values()
               for n, _, d in clip(ev, lo, hi)
               if module_name(n) in names) / 1e9
