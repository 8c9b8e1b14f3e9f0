"""Device time of every op that is not a Mosaic kernel per 1,000 rounds
verified in the traced window, in milliseconds."""


def read(rec):
    t = rec["trace"]
    if not t or not rec["rounds"] or not t["xla_s"]:
        return None
    return t["xla_s"] * 1e3 * 1000.0 / rec["rounds"]
