"""Share of the interconnect's roofline that staging's all-gather reaches,
in percent.

Least time: every chip receives the part of the replica it did not hold
(``ici_costs.py``) at the chip's published interconnect rate, the whole of
``ici_bits_per_s`` / 8, so that the share cannot read above 100%. Time
taken: the device time of every operation of the gather's program
(``jit_staging_all_gather``) in the window, its zeroing and its copies
into the replica included, over all chips against all chips' runs.
"""
import numpy as np

import trace_reduce
from ici_costs import all_gather_min_bytes_per_chip

MODULE = "jit_staging_all_gather"


def read(run):
    seconds, runs = trace_reduce.module_time(run.profile, MODULE)
    bits = run.peaks.get("ici_bits_per_s")
    if not seconds or not runs or not bits:
        return None
    cfg = run.cell.config
    replica = (cfg["frames"] * cfg["frame_size"] ** 2
               * np.dtype(cfg["dtype"]).itemsize)
    least = runs * all_gather_min_bytes_per_chip(replica, run.cell.chips)
    return 100.0 * least / (bits / 8) / seconds
