"""Window milliseconds a stored round spent outside the aggregator's four
spans (`agg.partials`, `agg.recover`, `agg.final_verify`, `agg.append`):
the window's host-clock time less the spans' seconds in it, over the
window's rounds.  The partials' arrival (the node's own signing and the
peers' delivery) and the aggregator's bookkeeping fall here.

Span seconds are deltas between the verify service's `stats()["spans"]`
snapshots before and after the window; nothing where the program keeps
none of these spans."""

SPANS = ("agg.partials", "agg.recover", "agg.final_verify", "agg.append")


def read(rec):
    s0, s1 = rec["stats0"].get("spans"), rec["stats1"].get("spans")
    if s0 is None or s1 is None or not rec["rounds"] \
            or not any(name in s1 for name in SPANS):
        return None
    inside = sum(s1.get(name, (0, 0.0))[1] - s0.get(name, (0, 0.0))[1]
                 for name in SPANS)
    return (rec["window_s"] - inside) * 1e3 / rec["rounds"]
