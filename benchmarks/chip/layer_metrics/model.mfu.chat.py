"""Model FLOPs of the real prompt and output tokens processed in the
traced window over the window's length times the chip's bf16 peak: the
whole step's share of the peak (``fqabench/yardstick.py``)."""

from fqabench import yardstick


def read(run):
    return yardstick.traced_mfu(run)
