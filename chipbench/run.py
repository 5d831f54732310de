#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip and print one JSON line.

    python3 chipbench/run.py --workload rw256-n4m.open-mixed-k10 \\
        --seed 7 --seconds 30 --trace 0

Set-up (data from the seed, the index, warm-up of every shape the window
uses) is timed from the start of this process; then the cell's traffic
runs for ``--seconds``; then what the window produced is compared with the
plain reference. ``--trace 1`` measures the same window under the
profiler and reports the cell's per-layer metrics instead of its
end-to-end ones. Without a TPU the run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# The TPU runtime logs to a fixed /tmp path unless told otherwise; a run
# writes nothing outside its checkout and its own temporary directory.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench import harness
    return harness.run(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
