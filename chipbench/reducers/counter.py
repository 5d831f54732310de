"""A number the driver read from the program's own counters or spans in
the window, by the name ``counter``."""


def read(spec, trace, counters, cell, device_kind):
    return counters.get(spec["counter"])
