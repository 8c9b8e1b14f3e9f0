"""Device passes of the partials program (the program's `partials.pass`
counter: one per RLC check, localisation passes included) over the
window's rounds.

The delta of the counter between the verify service's `stats()["spans"]`
snapshots before and after the window; nothing where the program keeps
no such counter."""

COUNTER = "partials.pass"


def read(rec):
    s0, s1 = rec["stats0"].get("spans"), rec["stats1"].get("spans")
    if s0 is None or s1 is None or COUNTER not in s1 or not rec["rounds"]:
        return None
    return (s1[COUNTER][0] - s0.get(COUNTER, (0, 0.0))[0]) / rec["rounds"]
