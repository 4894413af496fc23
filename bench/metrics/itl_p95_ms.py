"""95th percentile of every gap between consecutive tokens of one request,
both inside the window, in milliseconds."""
from bench import readings


def read(run):
    return readings.itl_p95_ms(run)
