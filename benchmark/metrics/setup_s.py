"""Seconds from process start to the window's start: imports, backend
init, the fixture, compile or cache load, lowering and the warm-up scan."""


def read(rec):
    return rec["setup_s"]
