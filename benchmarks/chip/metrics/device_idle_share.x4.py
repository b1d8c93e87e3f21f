"""Share of the window in which no operation ran on a chip, in percent,
averaged over the cell's chips: 1 - (union of a chip's op intervals /
traced window)."""
import trace_reduce


def read(run):
    return 100.0 * trace_reduce.idle_share(run.profile)
