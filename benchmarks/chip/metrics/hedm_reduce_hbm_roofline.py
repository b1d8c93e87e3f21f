"""Share of the HBM roofline that the stage-1 filter reaches, in percent.

Least time: the least bytes of every ``hedm_reduce`` call in the window
(frames as stored, the dark frame, uint8 masks, counts; ``costs.py``) at
the chip's peak HBM bandwidth. Time taken: the device time of every
operation of the ``jit_hedm_reduce`` program in the trace, the wrapper's
padded and gathered copies included. The filter is taken as bound by HBM
alone: no peak of the vector unit is published to bound it otherwise.
"""
import trace_reduce
from costs import hedm_reduce_min_bytes

MODULE = "jit_hedm_reduce"


def read(run):
    seconds, runs = trace_reduce.module_time(run.profile, MODULE)
    bandwidth = run.peaks.get("hbm_bytes_per_s")
    if not seconds or not runs or not bandwidth or not run.window.calls:
        return None
    cfg = run.cell.config
    frames = round(runs * run.window.work / run.window.calls)
    least = sum(hedm_reduce_min_bytes(f, cfg["frame_size"], cfg["frame_size"],
                                      cfg["dtype"], cfg["dtype"])
                for f in _split(frames, runs))
    return 100.0 * least / bandwidth / seconds


def _split(frames, runs):
    """``frames`` spread over ``runs`` calls as evenly as they came."""
    q, r = divmod(frames, runs)
    return [q + (i < r) for i in range(runs)]
