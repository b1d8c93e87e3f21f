"""XLA compilations per ``reduce_frames`` call inside the window, counted
from JAX's backend-compile events (a compile, or a fetch of a compiled
program from the persistent cache). Set-up warms every shape the window
uses, so this reads 0 until a change makes the program compile per call."""


def read(run):
    if not run.window.calls:
        return None
    return run.compiles / run.window.calls
