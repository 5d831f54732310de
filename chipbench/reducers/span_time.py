"""Host seconds under the program's named spans, per unit of a counter.

The union of the intervals of every span whose name the metric file's
``spans`` lists, in the window, over the driver's counter ``per``
(``builds``). None where the window holds none of them."""

from chipbench import spans


def read(spec, trace, counters, cell, device_kind):
    per = counters.get(spec["per"])
    if trace is None or not per:
        return None
    found = [e for name in spec["spans"] for e in spans.named(trace, name)]
    if not found:
        return None
    return spans.length(spans.clipped(trace, found)) / 1e9 / per
