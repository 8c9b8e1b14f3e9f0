"""Useful lanes over dispatched lanes in the window: deltas of the verify
service's `dispatch_lanes` and `dispatch_slots` counters."""


def read(rec):
    slots = rec["stats1"]["dispatch_slots"] - rec["stats0"]["dispatch_slots"]
    if slots <= 0:
        return None
    lanes = rec["stats1"]["dispatch_lanes"] - rec["stats0"]["dispatch_lanes"]
    return lanes / slots
