"""Percent of the traced window with no operation running on the device, in
the sparse-expert offline cell."""
from bench import readings


def read(run):
    return readings.device_idle_share(run)
