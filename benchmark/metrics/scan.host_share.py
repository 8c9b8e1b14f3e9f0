"""Share of the window the scan spent outside the verify handle's
`verify_batch` calls (store reads, linkage, bookkeeping), in percent,
from the benchmark's own spans."""


def read(rec):
    if not rec["window_s"]:
        return None
    outside = sum(t1 - t0 for name, t0, t1 in rec["spans"]
                  if name == "scan.outside")
    return 100.0 * outside / rec["window_s"]
