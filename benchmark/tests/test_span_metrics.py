"""The readers of the program's span totals (`stats()["spans"]`, taken
when set-up ends and after the window) on hand-built run records, and on
records of a program that keeps no spans."""

import pytest

from harness.spec import Spec

SPAN_READERS = {
    "scan.read_ms_per_kround": "integrity.read",
    "service.queue_ms_per_kround": "verify.queue",
    "service.pack_ms_per_kround": "verify.pack",
    "service.dispatch_ms_per_kround": "verify.dispatch",
    "service.return_ms_per_kround": "verify.return",
}


def _rec(spans0, spans1, rounds=2048):
    return {"rounds": rounds, "window_s": 2.4, "setup_s": 160.0,
            "stats0": {"dispatch_lanes": 512, "dispatch_slots": 8192,
                       "spans": spans0},
            "stats1": {"dispatch_lanes": 2560, "dispatch_slots": 40960,
                       "spans": spans1},
            "dispatches": 4, "trace": None}


@pytest.fixture(scope="module")
def spec():
    return Spec()


@pytest.mark.parametrize("metric,span", sorted(SPAN_READERS.items()))
def test_window_delta_per_kround(spec, metric, span):
    # four chunks of 512 rounds in the window, 0.1 s in the span in all
    rec = _rec({span: [10, 1.5], "batch.first_call": [1, 140.0]},
               {span: [14, 1.6], "batch.first_call": [1, 140.0]})
    assert spec.reader(metric)(rec) == pytest.approx(0.1e6 / 2048)
    # the span first fires inside the window
    rec = _rec({"batch.first_call": [1, 140.0]}, {span: [4, 0.1]})
    assert spec.reader(metric)(rec) == pytest.approx(0.1e6 / 2048)


@pytest.mark.parametrize("metric", sorted(SPAN_READERS) + [
    "batch.first_call_s"])
def test_nothing_to_read_is_nothing(spec, metric):
    """A program without the registry (no "spans" in its stats), or one
    whose span never fired, reads as no value, never as 0."""
    parent = _rec(None, None)
    del parent["stats0"]["spans"], parent["stats1"]["spans"]
    assert spec.reader(metric)(parent) is None
    assert spec.reader(metric)(_rec({}, {})) is None
    span = SPAN_READERS.get(metric)
    if span is not None:
        # fired in set-up, not in the window; or no round in the window
        assert spec.reader(metric)(_rec({span: [3, 1.0]},
                                        {span: [3, 1.0]})) is None
        assert spec.reader(metric)(_rec({span: [3, 1.0]}, {span: [4, 1.2]},
                                        rounds=0)) is None


def test_first_call_seconds_are_set_up_s(spec):
    rec = _rec({"batch.first_call": [1, 140.5],
                "batch.first_call/g1_rlc.raw_unchained@8192": [1, 140.5]},
               {"batch.first_call": [2, 150.0]})
    assert spec.reader("batch.first_call_s")(rec) == 140.5


def test_each_reader_is_a_per_layer_metric_of_both_cells(spec):
    entries = {m["name"]: m for m in spec.doc["per_layer"]}
    for metric in list(SPAN_READERS) + ["batch.first_call_s"]:
        m = entries[metric]
        assert m["source"] == "program_counter"
        assert m["workloads"] == ["quicknet.scan", "loe_default.scan"]
        assert m["moves"] == ("setup_s" if metric == "batch.first_call_s"
                              else "rounds_per_s")
