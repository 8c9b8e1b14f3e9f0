"""Host milliseconds a stored round spent in the aggregator's partial
verifier call (the program's `agg.partials` span: the live-lane queue and
the device passes of the partials program) over the window's rounds.

The delta of the span's seconds between the verify service's
`stats()["spans"]` snapshots before and after the window; nothing where
the program keeps no such span."""

SPAN = "agg.partials"


def read(rec):
    s0, s1 = rec["stats0"].get("spans"), rec["stats1"].get("spans")
    if s0 is None or s1 is None or SPAN not in s1 or not rec["rounds"]:
        return None
    n0, t0 = s0.get(SPAN, (0, 0.0))
    n1, t1 = s1[SPAN]
    if n1 == n0:
        return None
    return (t1 - t0) * 1e3 / rec["rounds"]
