"""XLA compilations per call into the program inside the window (each
staging of the scan and each step of the resident reduction is a call),
counted from JAX's backend-compile events. Set-up stages once and runs
every step's shape, so this reads 0 until a change makes the program
compile per call."""


def read(run):
    if not run.window.calls:
        return None
    return run.compiles / run.window.calls
