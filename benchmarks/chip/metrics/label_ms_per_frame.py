"""Host milliseconds per frame in ``label_components``, by the host clock
around each call (the scan driver wraps the function in traced runs)."""


def read(run):
    times = run.timers.get("bench.label")
    if not times:
        return None
    return 1e3 * sum(times) / len(times)
