#!/usr/bin/env python3
"""Chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the TPU chips the cell
asks for; the cells are those of ``BENCHMARK.json``. With ``--trace 0`` the
result's metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics. Exits non-zero, printing no result, where JAX finds no
TPU. See ``harness.py`` for what a run does.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import harness  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(harness.main(sys.argv[1:], ROOT, T_START))
