"""One run of one cell: set up, measure, check, print one JSON line.

The harness knows no cell: the manifest names the configuration, the
traffic (whose ``driver`` module sets up and drives the system) and the
per-layer metrics (whose ``reducer`` modules read them). A driver module
exposes ``Driver(config, traffic, seed, seconds, log)`` with:

  ``setup()``            make the data, build, warm up every shape;
  ``measure(tracer)``    the window; returns a :class:`Window`;
  ``release()``          free the program's state on the device;
  ``check()``            compare with the reference; returns
                         ``{name: (value, limit)}``, each value at most
                         its limit when correct.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import sys
import time

import numpy as np

from chipbench import manifest

TRACE_DIR = os.path.join(manifest.ROOT, ".chipbench", "trace")


@dataclasses.dataclass
class Window:
    """What a driver measured: end-to-end values, counts, counters."""

    metrics: dict  # end-to-end metric name -> value
    attempted: int
    failed: int
    counters: dict = dataclasses.field(default_factory=dict)
    notes: list = dataclasses.field(default_factory=list)


def log(line: str) -> None:
    """One progress line on standard error."""
    print(line, file=sys.stderr, flush=True)


def seeds(seed: int):
    """A JAX key and a NumPy generator, both from any whole ``seed``."""
    import jax
    import jax.numpy as jnp
    ss = np.random.SeedSequence(seed % (1 << 64))
    words = ss.generate_state(2, np.uint32)
    key = jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")
    return key, np.random.default_rng(ss)


def check_device(chips: int) -> dict:
    """The device as JAX reports it; exits unless it is a TPU with
    at least ``chips`` chips."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"JAX backend is {backend!r}, not 'tpu': this "
                         "benchmark runs on the chip only")
    devs = jax.devices()
    if len(devs) < chips:
        raise SystemExit(f"{len(devs)} TPU chips found, the cell needs "
                         f"{chips}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


class CompileCounter:
    """Backend compiles and their seconds, as JAX reports them."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs


class Tracer:
    """The profiler around a window, or nothing when tracing is off."""

    def __init__(self, enabled: bool, cell: str):
        self.enabled = enabled
        self.dir = os.path.join(TRACE_DIR, cell)
        self.path = None

    def start(self) -> None:
        """Start the profiler (host spans and device ops, no Python)."""
        if not self.enabled:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        """Stop the profiler and find the trace file it wrote."""
        if not self.enabled:
            return
        import glob
        import jax
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.dir, "**", "*.xplane.pb"), recursive=True))
        self.path = found[-1] if found else None


def _number(v):
    return v if isinstance(v, int) or math.isfinite(v) else str(v)


def run(args, t_start: float, check=check_device) -> int:
    """One run as ``chipbench/run.py`` is called; returns the exit code."""
    bench = manifest.load()
    cell = manifest.cell(bench, args.workload)
    device = check(cell.chips)
    log(f"[device] platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCounter()

    driver_mod = manifest.module("drivers", cell.traffic["driver"])
    drv = driver_mod.Driver(cell.config, cell.traffic, args.seed,
                            args.seconds, log)
    drv.setup()
    setup_s = time.perf_counter() - t_start
    log(f"[setup] {setup_s:.3f} s, {compiles.count} compiles "
        f"({compiles.seconds:.3f} s), cache {cache}")

    tracer = Tracer(bool(args.trace), cell.name)
    before = compiles.count
    window = drv.measure(tracer)
    in_window = compiles.count - before
    for line in window.notes + [f"[window] compiles inside: {in_window}"]:
        print(line, flush=True)
    peak = memory_peak_bytes()
    drv.release()
    checks = drv.check()
    correct = all(v <= lim for v, lim in checks.values())

    if args.trace:
        from chipbench import trace as trace_mod
        tr = trace_mod.load(tracer.path) if tracer.path else None
        metrics = {}
        for entry, spec in cell.per_layer:
            reader = manifest.module("reducers", spec["reducer"])
            value = reader.read(spec, tr, window.counters, cell,
                                device["kind"])
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
        if tr is not None:
            device["busy_s"] = tr.busy_s()
            device["window_s"] = tr.window_s()
    else:
        values = dict(window.metrics, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    device["memory_peak_bytes"] = peak
    result = {"correct": correct, "attempted": window.attempted,
              "failed": window.failed, "metrics": metrics, "device": device}
    if args.trace and tr is not None:
        result["breakdown"] = tr.breakdown()
    result["checks"] = {name: {"value": _number(v), "limit": lim}
                        for name, (v, lim) in checks.items()}
    for name, (v, lim) in checks.items():
        log(f"[check] {name} {v!r} limit {lim!r} "
            f"{'ok' if v <= lim else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0
