"""Host milliseconds per frame in stage 1's labeling: the program's
``hedm.label`` spans (a frame's mask compare and its runs with their
component labels) inside the window, over the frames the window reduced.
The span is the program's own, so it holds whichever labeling function
``reduce_frames`` calls."""
import host_spans


def read(run):
    secs = host_spans.seconds(host_spans.of(run), "hedm.label")
    if not secs or not run.window.work:
        return None
    return 1e3 * secs / run.window.work
