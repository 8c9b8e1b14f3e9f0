"""The trace reduction, on a hand-made trace and on a small recorded one."""

import os

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_reduce_hand_made():
    ms = 1e6
    ev = {"host": [("scan.outside", 0.0, 10 * ms),
                   ("scan.verify", 10 * ms, 90 * ms)],
          "devices": {"/device:TPU:0": [
              ("fusion.1", 12 * ms, 20 * ms, False),
              ("my_kernel", 30 * ms, 40 * ms, True),
              ("fusion.2", 60 * ms, 10 * ms, False),   # inside my_kernel
              ("fusion.3", 95 * ms, 10 * ms, False),   # half outside
              ("fusion.1", 200 * ms, 5 * ms, False)]}}  # outside
    r = trace.reduce(ev)
    assert r["window_s"] == pytest.approx(0.1)
    # busy: [12,32) [30,70) [95,100) -> 20 + 38 + 5
    assert r["busy_s"] == pytest.approx(0.063)
    # self times: my_kernel 40 - 10 (fusion.2 inside it); fusion.1's
    # 2 ms overlap with my_kernel's start is the part it encloses
    assert r["pallas_s"] == pytest.approx(0.030)
    assert r["xla_s"] + r["pallas_s"] == pytest.approx(r["busy_s"])
    assert r["device_ops"][0] == ["my_kernel", pytest.approx(0.030)]
    gaps = r["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.025, 0.012])
    assert gaps[0][0].startswith("scan.verify@0.070")
    assert gaps[1][0].startswith("scan.outside@0.000")


def test_reduce_needs_device_and_host():
    with pytest.raises(ValueError):
        trace.reduce({"host": [], "devices": {"d": []}})
    with pytest.raises(ValueError):
        trace.reduce({"host": [("scan.verify", 0.0, 1.0)], "devices": {}})


def test_reduce_recorded_chip_slice():
    """The head of a traced quicknet.scan window on a TPU v5 lite: the
    window's first scan.outside span and first chunk's scan.verify span,
    and the first 1,500 ops of the device's "XLA Ops" line."""
    r = trace.reduce(trace.load_events(
        os.path.join(DATA, "trace_quicknet_head.json")))
    assert r["window_s"] == pytest.approx(0.589741675)
    assert r["busy_s"] == pytest.approx(0.000215805)
    assert r["pallas_s"] == 0.0
    assert r["xla_s"] == pytest.approx(r["busy_s"])
    assert r["device_ops"][0] == ["dynamic_slice.9201",
                                  pytest.approx(4.2078e-05)]
    assert r["idle_gaps"][0] == ["scan.verify@0.023s",
                                 pytest.approx(0.566914949)]
