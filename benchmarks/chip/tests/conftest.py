"""Puts the benchmark's modules and the program on the path. These tests
run by path (``python -m pytest benchmarks/chip/tests``), on the CPU."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent.parent.parent / "src")]
