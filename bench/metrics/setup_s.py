"""Seconds from process start to the window's start: imports, the device,
weights, engine, compiles or compile-cache loads, warm-up and pre-roll."""


def read(run):
    return run.setup_s
