"""CPU rehearsal of the harness below the chip gate: every real traffic
mix and configuration, added to a tree of its own as tiny files, runs
through fixture, warm-up, window and check, and the metric readers read
what a run records."""

import time

import pytest

from helpers import tiny_tree


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return tiny_tree(tmp_path_factory.mktemp("bench"))


CELLS = ["tiny_quicknet.scan", "tiny_loe_default.scan",
         "tiny_quicknet.scan_corrupt"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_from_files(spec, cell):
    from harness.cell import run_cell
    r = run_cell(spec, cell, 2**31 + 99, 0.5, False, time.monotonic(),
                 device=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"rounds_per_s", "setup_s"}
    assert r["metrics"]["rounds_per_s"]["value"] > 0
    assert list(r)[-1] == "checks"


def test_same_seed_same_store(spec):
    from harness.fixture import ChainFixture
    cfg = spec.config("tiny_loe_default")
    tr = spec.traffic("scan_corrupt")
    chunk = cfg["config_overrides"]["sync_chunk"]
    a, b = (ChainFixture(cfg, tr, 2**33 + 5, chunk) for _ in range(2))
    assert a.sign(a.lo, a.lo + 9) == b.sign(b.lo, b.lo + 9)
    assert a.corrupt == b.corrupt and len(a.corrupt) == a.rounds // 128
    # each planted row lies in its block's first chunk
    assert all((r - a.first) % (2 * chunk) < chunk for r in a.corrupt)


def _rec(**kw):
    rec = {"rounds": 2048, "window_s": 4.0, "setup_s": 60.0,
           "spans": [("scan.outside", 0.0, 0.2), ("scan.verify", 0.2, 3.8),
                     ("scan.outside", 3.8, 4.0)],
           "stats0": {"dispatch_lanes": 512, "dispatch_slots": 8192},
           "stats1": {"dispatch_lanes": 2560, "dispatch_slots": 40960},
           "dispatches": 4,
           "trace": {"busy_s": 3.6, "window_s": 4.0, "pallas_s": 3.0,
                     "xla_s": 0.5}}
    rec.update(kw)
    return rec


def test_metric_readers(spec):
    want = {"rounds_per_s": 512.0, "setup_s": 60.0,
            "scan.host_share": 10.0, "service.fill_ratio": 0.0625,
            "batch.dispatches_per_kround": 1.953125,
            "device.idle_share": 10.0,
            "kernel.pallas_ms_per_kround": 1464.84375,
            "kernel.xla_ms_per_kround": 244.140625}
    for name, v in want.items():
        assert spec.reader(name)(_rec()) == pytest.approx(v)
    # a reader that finds nothing to read returns nothing, never 0
    for name in ("device.idle_share", "kernel.pallas_ms_per_kround",
                 "kernel.xla_ms_per_kround"):
        assert spec.reader(name)(_rec(trace=None)) is None
    assert spec.reader("service.fill_ratio")(
        _rec(stats1=_rec()["stats0"])) is None
