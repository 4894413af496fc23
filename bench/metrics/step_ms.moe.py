"""Window milliseconds per Engine.step started inside it (host clock), in
the sparse-expert offline cell."""
from bench import readings


def read(run):
    return readings.step_ms(run)
