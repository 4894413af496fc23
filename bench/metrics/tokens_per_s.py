"""Generated tokens that arrived inside the window, per second of the
window."""
from bench import readings


def read(run):
    return readings.tokens_per_s(run)
