"""Share of the engine's RDC rounds that ran narrow, in %.

The batcher's ``paris.flush.resolve`` span carries the engine's ``rounds``
(main loop and exactness scan) and, from a program whose rounds narrow to
the live rows, ``narrow_rounds``: how many of those ran over the live rows
alone. The share is 100 * sum(``narrow_rounds``) / sum(``rounds``) over
the spans in the window. None where no span carries ``narrow_rounds`` (a
program without narrow rounds) or none ran a round.
"""

from chipbench import spans


def read(spec, trace, counters, cell, device_kind):
    if trace is None:
        return None
    found = [e for e in spans.named(trace, "paris.flush.resolve")
             if "narrow_rounds" in e.stats]
    rounds = sum(float(e.stats.get("rounds", 0)) for e in found)
    if not rounds:
        return None
    narrow = sum(float(e.stats["narrow_rounds"]) for e in found)
    return 100.0 * narrow / rounds
