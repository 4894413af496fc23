"""95th percentile over every request due inside the window of the time
from its scheduled arrival to its first token, in milliseconds; a
request that never got one counts as unbounded."""
from bench import readings


def read(run):
    return readings.ttft_p95_ms(run)
