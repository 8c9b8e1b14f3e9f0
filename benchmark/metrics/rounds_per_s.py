"""Beacon rounds whose verdict the scan received in the window, over the
window's elapsed time (host clock; the window closes at the first chunk
verdict past --seconds)."""


def read(rec):
    if not rec["window_s"]:
        return None
    return rec["rounds"] / rec["window_s"]
