"""The lower-bound kernel's share of its roofline, in %.

Each device op whose name the metric file's ``op`` matches is one launch:
one pass of the router's padded batch over one shard's rows. Its work is
``chipbench.cost.lbc`` at the cell's configuration: the bucket (the
router's ``max_batch`` rounded up to a power of two, at least
``min_bucket``), the shard's rows and the index's segments. The share is
the least time the chip's peaks allow for all launches over their device
time; above 100% it raises.
"""

from chipbench import cost, peaks


def read(spec, trace, counters, cell, device_kind):
    if trace is None:
        return None
    times = trace.op_seconds(spec["op"])
    if not times:
        return None
    r = cell.config["router"]
    bucket = max(r["min_bucket"], 1 << (r["max_batch"] - 1).bit_length())
    rows = -(-cell.config["data"]["num_series"] // r["shards"])
    ops, moved = cost.lbc(bucket, rows, cell.config["index"]["segments"])
    return peaks.roofline_pct(ops * len(times), moved * len(times),
                              sum(times), device_kind)
