"""MB (1e6 bytes) of NumPy inputs handed to the kernel factory's device
impls per window, every one of which copies them to the chip: the
``kernel.h2d_bytes.<op>`` counters over ``windows``.  A mean over every
window the process executed, the warm-up windows included; set-up's
shape warm-up calls add to the bytes too."""
from harness.spans import counter_per_window


def read(run):
    b = counter_per_window("kernel.h2d_bytes")
    return None if b is None else b / 1e6
