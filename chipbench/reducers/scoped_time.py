"""Device time under one of the engine's named scopes, in ms per batch.

An op is under the metric file's ``scope`` when its op path
(:mod:`chipbench.xplane_meta`) has the scope as a component, as
``jit(_index_engine)/paris.rdc/while/body/...`` has ``paris.rdc``. The time
is the union of those ops' intervals in the window, averaged over devices,
over the driver's counter ``per``. Where a while op has no op path in
the trace, its body's ops stand for it, which leaves out only the loop's
own control between them. A window in which no op carries a ``paris.`` scope
(a program built without them) reads None; one whose engine ran no op
under this scope (no batch took the fallback) reads 0.0.
"""

from chipbench import spans, xplane_meta

SCOPE_PREFIX = "paris."


def read(spec, trace, counters, cell, device_kind):
    per = counters.get(spec["per"])
    if trace is None or not per:
        return None
    paths = xplane_meta.for_cell(cell.name)
    parts = {e.name: paths.get(e.name, "").split("/") for e in trace.ops}
    if not any(p.startswith(SCOPE_PREFIX) for ps in parts.values()
               for p in ps):
        return None
    total = 0.0
    for dev in trace.devices:
        mine = [e for e in trace.ops
                if e.where == dev and spec["scope"] in parts[e.name]]
        total += spans.length(spans.clipped(trace, mine))
    return total / len(trace.devices) / 1e6 / per
