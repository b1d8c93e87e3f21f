"""Run one benchmark cell once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``,
each number compared with the reference beside its limit. The same checks
end standard error. Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits non-zero.
"""
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent.parent / "src")]

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
