"""Least time of the traced SC-GEMM calls at their logical shapes over
their device time in the trace, in percent."""
from bench import readings


def read(run):
    return readings.sc_gemm_roofline(run)
