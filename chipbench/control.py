#!/usr/bin/env python3
"""Readings of the control: the reference in bfloat16 in the program's place.

    python3 chipbench/control.py --workload rw256-n4m.closed-hard-k100 \\
        --seeds 11,12,13 --seconds 30

For each seed it makes the cell's collection and queries as a run with
that seed and window does (a query cell's are the same for every seed, in
the seed's order), and compares with the float32 reference what
the same brute force gives in bfloat16 (for a build
cell: the SAX words and leaf order from rounded inputs). Each number
printed must be above the cell's limit for the control to fail, as it
has to. It runs on the chip only; the benchmark's own runs never run it.
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
    import jax
    import numpy as np
    from chipbench import harness, manifest, reference
    key, rng = harness.seeds(seed)
    if "k" not in cell.traffic:  # a build cell: its collection is the seed's
        data = cell.config["data"]
        gen = manifest.module("generators", data["generator"])
        raw = gen.series(jax.random.split(key, 3)[0], data["num_series"],
                         data["series_length"])
        ix = cell.config["index"]
        host = np.asarray(raw)
        want = reference.index_reference(host, ix["segments"],
                                         ix["cardinality"], ix["refine_bits"])
        low = reference.index_reference(host, ix["segments"],
                                        ix["cardinality"], ix["refine_bits"],
                                        low=True)
        return reference.compare_index(low["sax"][low["order"]],
                                       low["order"], want)
    drv = manifest.module("drivers", cell.traffic["driver"]).Driver(
        cell.config, cell.traffic, seed, seconds, harness.log)
    count = (len(drv.offsets) if hasattr(drv, "offsets")
             else cell.traffic["pool"])
    raw = drv.served.collection()
    qs = drv.served.query_pool(raw, count)
    pick = sorted(rng.choice(count, min(count,
                                        cell.traffic["check"]["sample"]),
                             replace=False))
    k = cell.traffic["k"]
    ref_d, ref_p = reference.knn(raw, qs[pick], k + 1)
    low_d, low_p = reference.knn(raw, qs[pick], k, low=True)
    return reference.compare_knn(low_d, low_p, ref_d, ref_p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
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
