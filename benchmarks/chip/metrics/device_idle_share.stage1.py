"""Share of the stage-1 window in which no operation ran on the chip, in
percent: 1 - (union of device op intervals / traced window)."""
import trace_reduce


def read(run):
    return 100.0 * trace_reduce.idle_share(run.profile)
