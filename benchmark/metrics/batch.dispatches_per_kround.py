"""Device program calls (RLC, bisection and exact passes: the delta of
`batch.dispatch_count()`) per 1,000 rounds verified in the window."""


def read(rec):
    if not rec["rounds"]:
        return None
    return rec["dispatches"] * 1000.0 / rec["rounds"]
