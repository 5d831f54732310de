"""Per-layer metric readers, one module per kind, named by a metric file's
``reducer``. Each exposes ``read(spec, trace, counters, cell,
device_kind)``: the metric's file, the window's
:class:`chipbench.trace.Trace` (None with tracing off), the driver's
counters, the :class:`chipbench.manifest.Cell` (its configuration and
traffic give the shapes a cost function needs) and JAX's device kind. It
returns a number, or None where it finds nothing to read."""
