"""Device-idle time inside the program's spans called ``span``, in % of
the traced window: the window's idle gaps (first device) that the union
of those spans covers. None where the window holds no such span."""

from chipbench import spans


def read(spec, trace, counters, cell, device_kind):
    if trace is None or not trace.ops or trace.window_s() <= 0:
        return None
    inside = spans.clipped(trace, spans.named(trace, spec["span"]))
    if not inside:
        return None
    idle = spans.overlap(inside, trace.idle_gaps())
    return 100.0 * idle / (trace.end - trace.start)
