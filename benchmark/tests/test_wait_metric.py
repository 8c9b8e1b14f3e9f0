"""`scan.wait_ms_per_kround`, the reader of the program's `integrity.wait`
span (the scan's caller blocked on its next chunk from the store
reader), on hand-built run records and on records of a program that
keeps no such span."""

import pytest

from harness.spec import Spec

METRIC, SPAN = "scan.wait_ms_per_kround", "integrity.wait"


def _rec(spans0, spans1, rounds=2048):
    return {"rounds": rounds, "window_s": 2.4, "setup_s": 160.0,
            "stats0": {"spans": spans0}, "stats1": {"spans": spans1}}


@pytest.fixture(scope="module")
def read():
    return Spec().reader(METRIC)


@pytest.mark.parametrize("spans0,spans1", [
    ({SPAN: [10, 1.5], "integrity.read": [10, 0.2]},
     {SPAN: [14, 1.6], "integrity.read": [14, 0.3]}),
    # the span first fires inside the window
    ({"integrity.read": [10, 0.2]}, {SPAN: [4, 0.1]}),
])
def test_window_delta_per_kround(read, spans0, spans1):
    # four chunks of 512 rounds in the window, 0.1 s waited in all
    assert read(_rec(spans0, spans1)) == pytest.approx(0.1e6 / 2048)


@pytest.mark.parametrize("rec", [
    # a program without the registry
    {"rounds": 2048, "stats0": {}, "stats1": {}},
    # a program whose scanner keeps no `integrity.wait` (the serial one)
    _rec({"integrity.read": [3, 1.0]}, {"integrity.read": [7, 1.1]}),
    # fired in set-up, not in the window; or no round in the window
    _rec({SPAN: [3, 1.0]}, {SPAN: [3, 1.0]}),
    _rec({SPAN: [3, 1.0]}, {SPAN: [4, 1.2]}, rounds=0),
])
def test_nothing_to_read_is_nothing(read, rec):
    assert read(rec) is None


def test_a_per_layer_metric_of_both_scan_cells():
    m = {m["name"]: m for m in Spec().doc["per_layer"]}[METRIC]
    assert (m["source"], m["layer"], m["moves"]) == (
        "program_counter", "chain scan", "rounds_per_s")
    assert m["workloads"] == ["quicknet.scan", "loe_default.scan"]
