#!/usr/bin/env python3
"""Readings of the control for a cell served over per-chip shares.

    python3 chipbench/control_sharded.py --workload rw256-n16m.mesh4 \\
        --seeds 11,12 --seconds 45

As ``control.py`` does for a one-chip query cell: for each seed it makes
the cell's shares and query pool as a run with that seed and window does,
and compares with the float32 per-share reference
(:mod:`chipbench.reference_sharded`) what the same brute force gives in
bfloat16. Each number printed must be above the cell's limit for the
control to fail, as it has to. It runs on the chip only; the benchmark's
own runs never run it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def readings(cell, seed: int, seconds: float) -> dict:
    """The control's compared numbers for one seed."""
    from chipbench import harness, manifest, reference, reference_sharded
    rng = harness.seeds(seed)[1]
    drv = manifest.module("drivers", cell.traffic["driver"]).Driver(
        cell.config, cell.traffic, seed, seconds, harness.log)
    count = len(drv.offsets)
    shares = drv.served.collection()
    qs = drv.served.query_pool(shares, count)
    pick = sorted(rng.choice(count, min(count,
                                        cell.traffic["check"]["sample"]),
                             replace=False))
    k = cell.traffic["k"]
    ref_d, ref_p = reference_sharded.knn(shares, qs[pick], k + 1)
    low_d, low_p = reference_sharded.knn(shares, qs[pick], k, low=True)
    return reference.compare_knn(low_d, low_p, ref_d, ref_p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    args = ap.parse_args(argv)
    from chipbench import harness, manifest
    cell = manifest.cell(manifest.load(), args.workload)
    harness.check_device(cell.chips)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        found = readings(cell, seed, args.seconds)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": found,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
