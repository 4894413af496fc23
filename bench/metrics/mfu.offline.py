"""Model FLOPs of the token rows computed in the traced window over the
window times the chip's bf16 peak, in percent."""
from bench import readings


def read(run):
    return readings.mfu(run)
