"""What the batcher's flush spans recorded in the window.

The program's ``paris.flush`` span carries ``qn`` (real queries),
``bucket`` and its cohort's summed and longest queue wait, submit to
claim (``wait_ms_sum``, ``wait_ms_max``); ``paris.flush.resolve``, opened
once the answers are on the host, carries ``qn``, the engine's ``reads``
(raw rows read for the real queries), ``rounds`` and the shard's ``rows``.
The metric file's ``quantity`` picks the number:

  ``rounds``     mean rounds per resolved batch;
  ``reads_pct``  100 * sum(reads) / sum(qn * rows);
  ``wait_ms``    sum(wait_ms_sum) / sum(qn) over the flushes.

None where the window holds no such span (a program without them).
"""

from chipbench import spans


def _stat(e, key):
    return float(e.stats.get(key, 0))


def read(spec, trace, counters, cell, device_kind):
    if trace is None:
        return None
    quantity = spec["quantity"]
    name = "paris.flush" if quantity == "wait_ms" else "paris.flush.resolve"
    found = [e for e in spans.named(trace, name) if "qn" in e.stats]
    if not found:
        return None
    qn = sum(_stat(e, "qn") for e in found)
    if quantity == "wait_ms":
        return sum(_stat(e, "wait_ms_sum") for e in found) / qn
    if quantity == "rounds":
        return sum(_stat(e, "rounds") for e in found) / len(found)
    if quantity == "reads_pct":
        rows = sum(_stat(e, "qn") * _stat(e, "rows") for e in found)
        return 100.0 * sum(_stat(e, "reads") for e in found) / rows
    raise ValueError(f"unknown quantity {quantity!r}")
