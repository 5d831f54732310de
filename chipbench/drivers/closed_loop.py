"""Closed loop: ``clients`` callers, each sending its next query as soon as
its last one is answered, until the window closes.

The next query is submitted from the answer's own callback, so the load
comes from no thread of the harness. Throughput is the queries answered
inside the window over the window's length. Queries are drawn in turn
from a seeded pool of ``pool`` queries.
"""

from __future__ import annotations

import threading
import time

import jax

from chipbench import harness
from chipbench.drivers.serving import RESULT_WAIT_S, Served


class Driver:
    """One closed-loop query cell."""

    def __init__(self, config, traffic, seed, seconds, log):
        self.served = Served(config, traffic, seed, log)
        self.seconds = seconds
        self.clients = int(traffic["clients"])
        self.pool = int(traffic["pool"])
        self.lock = threading.Lock()
        self.idle = threading.Event()
        self.next_id = 0
        self.in_flight = 0
        self.in_window = 0
        self.close = float("inf")

    def setup(self):
        self.served.setup(self.pool)

    def _send(self):
        with self.lock:
            rid = self.next_id
            self.next_id += 1
            self.in_flight += 1
            self.served.attempted += 1
        self.served.submit(rid, self._on_done)

    def _on_done(self, rid, now, exc):
        with self.lock:
            self.in_flight -= 1
            if exc is None and now <= self.close:
                self.in_window += 1
            more = now < self.close
            if not more and self.in_flight == 0:
                self.idle.set()
        if more:
            self._send()

    def measure(self, tracer):
        s = self.served
        before = s.router.stats()
        tracer.start()
        with jax.profiler.TraceAnnotation("chipbench.window"):
            t0 = time.perf_counter()
            self.close = t0 + self.seconds
            for _ in range(self.clients):
                self._send()
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                time.sleep(max(0.0, self.close - time.perf_counter()))
                self.idle.wait(RESULT_WAIT_S)
        after = s.router.stats()
        tracer.stop()
        batches = after["batches"] - before["batches"]
        answered = after["answered"] - before["answered"]
        notes = [f"[closed_loop] {self.in_window} answered inside the "
                 f"window, {answered} in all, of {s.attempted} sent; "
                 f"{batches} batches"]
        counters = {"batches": batches}
        return harness.Window(
            {"queries_per_s": self.in_window / self.seconds}, s.attempted,
            s.attempted - len(s.answers), counters, notes)

    def release(self):
        self.served.release()

    def check(self):
        return self.served.check()
