#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell to find its knee.

    python3 chipbench/knee.py --workload rw256-n4m.open-mixed-k10 \\
        --seed 5 --seconds 20 --rates 40,50,60,70

One process sets the cell up once, then runs one open-loop window per
rate, lowest first, with the cell's query mix and k. For each rate it
prints one JSON line: latency p50 and p95 from the scheduled send time,
the p95 of each quarter of the arrivals, and how long after the window's
close the last answer came. The knee is the highest rate whose quarters
do not rise and whose last answer comes within about one batch of the
close; a cell's traffic file offers 0.8 of it. It runs on the chip only;
the benchmark's own runs never run it.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    import argparse
    import json

    import numpy as np
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from chipbench import harness, manifest
    from chipbench.drivers import open_loop
    cell = manifest.cell(manifest.load(), args.workload)
    harness.check_device(cell.chips)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    rates = sorted(float(r) for r in args.rates.split(","))
    top = open_loop.Driver(cell.config, dict(cell.traffic, rate_qps=rates[-1]),
                           args.seed, args.seconds, harness.log)
    top.setup()
    served = top.served
    for rate in rates:
        served.answers.clear()
        served.attempted = 0
        drv = open_loop.Driver(cell.config, dict(cell.traffic, rate_qps=rate),
                               args.seed, args.seconds, harness.log, served)
        win = drv.measure(harness.Tracer(False, cell.name))
        close_to_last = float(np.nanmax(drv.done_at) - drv.t0 - args.seconds)
        print(json.dumps({"rate_qps": rate, **win.metrics,
                          "attempted": win.attempted, "failed": win.failed,
                          "batches": win.counters.get("batches"),
                          "last_answer_after_close_s": close_to_last,
                          "notes": win.notes}), flush=True)
    served.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
