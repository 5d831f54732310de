"""Open loop: queries sent on a Poisson schedule, whatever is answered.

The traffic file gives ``rate_qps``; a window of S seconds holds
``round(rate * S)`` arrivals. Their gaps are the exponential quantiles
``-ln(1 - (i + 1/2) / M) / rate``, shuffled by the seed: every seed sends
the same set of gaps and the same number of each query kind, in another
order. Each query is timed from its scheduled send time to the moment its
future resolves, so a stall also delays what was due behind it. How late
the sender ran is printed on an earlier line.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from chipbench import harness
from chipbench.drivers.serving import RESULT_WAIT_S, Served


class Driver:
    """One open-loop query cell."""

    def __init__(self, config, traffic, seed, seconds, log, served=None):
        self.served = served or Served(config, traffic, seed, log)
        self.seconds = seconds
        rate = float(traffic["rate_qps"])
        m = max(1, int(round(rate * seconds)))
        gaps = -np.log1p(-(np.arange(m) + 0.5) / m) / rate
        self.offsets = np.cumsum(self.served.rng.permutation(gaps))
        self.done_at = np.full(m, np.nan)
        self.all_done = threading.Event()
        self.left = m

    def setup(self):
        self.served.setup(len(self.offsets))

    def _on_done(self, rid, now, exc):
        if exc is None:
            self.done_at[rid] = now
        with self.served.lock:
            self.left -= 1
            last = self.left == 0
        if last:
            self.all_done.set()

    def measure(self, tracer):
        s = self.served
        m = len(self.offsets)
        sent = np.empty(m)
        before = s.router.stats()
        tracer.start()
        with jax.profiler.TraceAnnotation("chipbench.window"):
            self.t0 = t0 = time.perf_counter()
            due = t0 + self.offsets
            for rid in range(m):
                delay = due[rid] - time.perf_counter()
                if delay > 0:
                    with jax.profiler.TraceAnnotation("chipbench.sleep"):
                        time.sleep(delay)
                sent[rid] = time.perf_counter()
                with jax.profiler.TraceAnnotation("chipbench.submit"):
                    s.submit(rid, self._on_done)
                s.attempted += 1
            close = t0 + self.seconds
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                self.all_done.wait(
                    max(0.0, close + RESULT_WAIT_S - time.perf_counter()))
        after = s.router.stats()
        tracer.stop()
        lat_all = (self.done_at - due) * 1e3
        lat = lat_all[np.isfinite(lat_all)]
        quarters = [np.nanpercentile(q, 95) if np.isfinite(q).any()
                    else np.nan for q in np.array_split(lat_all, 4)]
        late = (sent - due) * 1e3
        answered = after["answered"] - before["answered"]
        padded = after["padded_queries"] - before["padded_queries"]
        batches = after["batches"] - before["batches"]
        notes = [
            f"[open_loop] sender lateness ms: p50 {np.median(late):.3f} "
            f"p99 {np.percentile(late, 99):.3f} max {late.max():.3f}",
            "[open_loop] p95 ms by quarter of arrivals (a growing backlog "
            "rises): " + " ".join(f"{q:.1f}" for q in quarters),
            f"[open_loop] {len(lat)} answered of {m}, {batches} batches, "
            f"last answer {np.nanmax(self.done_at, initial=t0) - t0:.3f} s "
            "after the start",
        ]
        metrics = {}
        if len(lat):
            metrics = {"latency_p50_ms": float(np.percentile(lat, 50)),
                       "latency_p95_ms": float(np.percentile(lat, 95))}
        counters = {"batches": batches}
        if answered + padded:
            counters["batch_fill_pct"] = 100.0 * answered / (answered + padded)
        return harness.Window(metrics, m, m - len(lat), counters, notes)

    def release(self):
        self.served.release()

    def check(self):
        return self.served.check()
