"""Device time of Mosaic (Pallas `tpu_custom_call`) ops per 1,000 rounds
verified in the traced window, in milliseconds."""


def read(rec):
    t = rec["trace"]
    if not t or not rec["rounds"] or not t["pallas_s"]:
        return None
    return t["pallas_s"] * 1e3 * 1000.0 / rec["rounds"]
