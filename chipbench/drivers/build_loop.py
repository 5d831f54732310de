"""Build loop: whole builds of the collection, back to back.

Set-up makes the collection on the device from the seed, copies it to a
host array (the build's input, as a file read into memory would be) and
runs one whole build to warm up every shape. In the window each build
starts after the previous index is dropped, and a build starts only while
the last one's time still fits before the window closes. The rate is the
series of all builds over the time from the window's start to the end of
the last build. The last index is compared with the reference: SAX words,
positions and leaf order.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from chipbench import harness, manifest, reference


class Driver:
    """One build cell."""

    def __init__(self, config, traffic, seed, seconds, log):
        self.config, self.traffic, self.log = config, traffic, log
        self.seconds = seconds
        key, self.rng = harness.seeds(seed)
        self.k_data = jax.random.split(key, 3)[0]
        data = config["data"]
        self.n, self.length = data["num_series"], data["series_length"]
        self.gen = manifest.module("generators", data["generator"])
        self.raw = None
        self.index = None

    def _build(self):
        from repro.core import PipelineBuilder, SeriesSource
        ix, b = self.config["index"], self.traffic["build"]
        builder = PipelineBuilder(
            ix["segments"], ix["cardinality"], mode=b["mode"],
            n_workers=b["workers"], refine_bits=ix["refine_bits"],
            impl=self.config["impl"])
        index, _ = builder.build(SeriesSource.from_array(
            self.raw, chunk_series=b["chunk_series"]))
        jax.block_until_ready(index)
        return index

    def setup(self):
        t = time.perf_counter()
        self.raw = np.asarray(self.gen.series(self.k_data, self.n,
                                              self.length))
        self.log(f"[setup] data {self.n} x {self.length} on the host in "
                 f"{time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        self.index = self._build()
        self.log(f"[setup] warm-up build in {time.perf_counter() - t:.3f} s")

    def measure(self, tracer):
        durations = []
        tracer.start()
        with jax.profiler.TraceAnnotation("chipbench.window"):
            t0 = time.perf_counter()
            close = t0 + self.seconds
            end = t0
            while not durations or end + durations[-1] <= close:
                self.index = None
                gc.collect()
                start = time.perf_counter()
                with jax.profiler.TraceAnnotation("chipbench.build"):
                    self.index = self._build()
                end = time.perf_counter()
                durations.append(end - start)
        tracer.stop()
        builds = len(durations)
        notes = [f"[build_loop] {builds} builds of {self.n} series, each "
                 + ", ".join(f"{d:.3f}" for d in durations)
                 + f" s; {end - t0:.3f} s from the window's start"]
        counters = {"builds": builds}
        return harness.Window(
            {"build_series_per_s": builds * self.n / (end - t0)}, builds, 0,
            counters, notes)

    def release(self):
        self.sax = np.asarray(self.index.sax)
        self.pos = np.asarray(self.index.pos)
        self.index = None
        gc.collect()

    def check(self):
        t = time.perf_counter()
        ix = self.config["index"]
        ref = reference.index_reference(self.raw, ix["segments"],
                                        ix["cardinality"], ix["refine_bits"])
        found = reference.compare_index(self.sax, self.pos, ref)
        self.log(f"[check] index of {self.n} series compared in "
                 f"{time.perf_counter() - t:.3f} s")
        limits = self.traffic["check"]["limits"]
        return {name: (value, limits[name]) for name, value in found.items()}
