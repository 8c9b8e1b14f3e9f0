"""Host milliseconds the scan's caller spent blocked waiting for its next
chunk from the store reader (the program's `integrity.wait` span: about
0 while the read of chunk k+1 hides behind the verify of chunk k) per
1,000 rounds verified in the window.

The delta of the span's seconds between the verify service's
`stats()["spans"]` snapshots before and after the window; nothing where
the program keeps no such span."""

SPAN = "integrity.wait"


def read(rec):
    s0, s1 = rec["stats0"].get("spans"), rec["stats1"].get("spans")
    if s0 is None or s1 is None or SPAN not in s1 or not rec["rounds"]:
        return None
    n0, t0 = s0.get(SPAN, (0, 0.0))
    n1, t1 = s1[SPAN]
    if n1 == n0:
        return None
    return (t1 - t0) * 1e6 / rec["rounds"]
