"""Host milliseconds per frame in the centroids of stage 1, the index grid
included: the program's ``hedm.centroids`` spans (a frame's intensity
moments and peak list) and ``hedm.index_grid`` spans (the ``(y, x)`` grid,
once a call) inside the window, over the frames the window reduced."""
import host_spans


def read(run):
    secs = host_spans.seconds(host_spans.of(run), "hedm.centroids",
                              "hedm.index_grid")
    if not secs or not run.window.work:
        return None
    return 1e3 * secs / run.window.work
