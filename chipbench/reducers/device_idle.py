"""Share of the traced window in which no op ran on the device, in %."""


def read(spec, trace, counters, cell, device_kind):
    if trace is None or not trace.ops or trace.window_s() <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s())
