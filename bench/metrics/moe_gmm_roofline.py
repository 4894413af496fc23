"""Least time of the traced window's routed-expert work (operations at the
bf16 peak or expert weights read at the HBM peak, whichever is longer) over
the device time of the grouped-matmul kernel in the trace, in percent."""
from bench import work_moe


def read(run):
    return work_moe.gmm_roofline(run)
