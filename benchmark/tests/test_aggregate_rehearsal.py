"""CPU rehearsal of the `aggregate` mix below the chip gate: a tiny group
(4 nodes, threshold 3) of the real configuration runs through fixture,
node, warm-up, window and check with the device partials verifier on the
CPU backend, and every new metric reader reads what the run records.  A
verifier that accepts every partial is caught."""

import json
import os
import time

import pytest

from harness.spec import Spec
from helpers import tiny_tree

CELL = "tiny_loe_default_group.aggregate"
SEED = 2**33 + 21
NEW = ("agg.partials_ms_per_round", "agg.passes_per_round",
       "agg.recover_ms_per_round", "agg.final_verify_ms_per_round",
       "agg.outside_ms_per_round", "agg.first_call_s")


class EveryMetric(Spec):
    """Reports the per-layer metrics in an untraced run too."""

    def metrics(self, workload, traced):
        return super().metrics(workload, False) \
            + super().metrics(workload, True)


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    tiny = tiny_tree(tmp_path_factory.mktemp("bench"))
    bench = tiny.bench_dir
    path = os.path.join(bench, "configs", "tiny_loe_default_group.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(group_size=4, threshold=3, rounds=64)
    with open(path, "w") as f:
        json.dump(cfg, f)
    path = os.path.join(bench, "traffic", "aggregate.json")
    with open(path) as f:
        mix = json.load(f)
    mix.update(warm_rounds=8, offline_peers=0, invalid_every=4)
    with open(path, "w") as f:
        json.dump(mix, f)
    return EveryMetric(os.path.join(os.path.dirname(bench), "BENCHMARK.json"),
                       bench)


def _run(spec, wrap=None, seconds=3.0):
    from harness.cell import run_cell
    return run_cell(spec, CELL, SEED, seconds, False, time.monotonic(),
                    device=False, verify_wrap=wrap)


@pytest.fixture(scope="module", autouse=True)
def cpu_backend_times():
    """The CPU backend compiles the G2 partials program in about half an
    hour, near the verify service's 30 minutes for a compiling dispatch
    and the loop's for the first verdict, and runs a pass in seconds, not
    milliseconds: neither may read as a fault or a stall here."""
    from drand_tpu.crypto import verify_service
    from harness.loops import aggregate
    limit, verify_service.DEFAULT_COMPILE_LIMIT = \
        verify_service.DEFAULT_COMPILE_LIMIT, 4 * 3600.0
    stall, aggregate.STALL_S = aggregate.STALL_S, 120.0
    warm, aggregate.WARM_STALL_S = aggregate.WARM_STALL_S, 4 * 3600.0
    yield
    aggregate.WARM_STALL_S = warm
    aggregate.STALL_S = stall
    verify_service.DEFAULT_COMPILE_LIMIT = limit


@pytest.fixture(scope="module")
def sound(spec):
    return _run(spec)


def test_cell_runs_correct_from_files(sound):
    assert sound["correct"], sound["checks"]
    assert set(sound["checks"]) == {
        "unanswered", "beacon_mismatch", "partial_verdict_mismatch",
        "partials_fell_back", "anchor_mismatch", "fell_back"}
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["metrics"]["rounds_per_s"]["value"] > 0


def test_every_new_reader_reads_the_run(sound):
    got = sound["metrics"]
    for name in NEW:
        assert isinstance(got[name]["value"], float), name
    # at least one pass a round; a planted round (one in 4) takes more
    assert got["agg.passes_per_round"]["value"] >= 1.0
    assert got["agg.first_call_s"]["value"] > 0


def test_idle_share_reads_a_trace(spec):
    rec = {"trace": {"busy_s": 1.5, "window_s": 2.0}}
    assert spec.reader("agg.device_idle_share")(rec) == pytest.approx(25.0)
    assert spec.reader("agg.device_idle_share")({"trace": None}) is None


def test_readers_of_a_program_without_the_spans_read_nothing(spec):
    rec = {"rounds": 10, "window_s": 1.0, "trace": None,
           "stats0": {"spans": {}}, "stats1": {"spans": {}}}
    for name in NEW:
        assert spec.reader(name)(rec) is None, name


def accept_all(_verify, _fx):
    return lambda msg, partials: [True] * len(partials)


def test_verifier_that_accepts_everything_is_caught(spec):
    r = _run(spec, accept_all)
    assert not r["correct"]
    assert r["checks"]["partial_verdict_mismatch"]["value"] > 0
