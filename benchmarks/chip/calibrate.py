"""Readings from which the limits of a cell's comparison are set.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,...,12 --control-seeds 1,2,3 --seconds 10

For each seed, in one process: the cell's set-up, a window of the program
and the cell's check (the lower readings); for each control seed also a
window of the control in the program's place on the same inputs (the
upper readings; ``controls.py``). Prints one JSON line per reading. It
needs a TPU, as the benchmark does.
"""
import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent.parent / "src")]

import controls  # noqa: E402
import harness  # noqa: E402


def _sides(with_control: bool):
    from repro.hedm import pipeline
    yield "program", contextlib.nullcontext()
    if with_control:
        yield "control", controls.in_place_of(
            pipeline, "reduce_frames_online", controls.scan_control())


def calibrate(cell, seeds, control_seeds, seconds):
    driver = harness.load_module(harness.HERE / "drivers"
                                 / f"{cell.spec['driver']}.py")
    for seed in seeds:
        ctx = harness.Context(cell=cell, seed=seed, trace=False)
        t0 = time.perf_counter()
        state = driver.setup(ctx)
        setup_s = time.perf_counter() - t0
        windows = []
        for side, patch in _sides(seed in control_seeds):
            with patch:
                windows.append((side, driver.window(ctx, state, seconds)))
        driver.release(state)
        for side, win in windows:
            checks = driver.check(ctx, state, win)
            print(json.dumps({
                "cell": cell.name, "seed": seed, "side": side,
                "work": win.work, "elapsed_s": win.elapsed,
                "setup_s": setup_s,
                "readings": {c.name: c.value for c in checks},
                "passes": all(c.ok for c in checks)}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    harness.pin_allocator()
    cell = harness.load_cell(args.workload)
    harness.accelerator(cell.chips)
    harness.use_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    calibrate(cell, seeds, control, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
