"""Share of the traced aggregation window in which no operation ran on the
device, in percent: 1 - (union of device op intervals) / window, from the
trace, as `device.idle_share` reduces it for the scan cells."""


def read(rec):
    t = rec["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
