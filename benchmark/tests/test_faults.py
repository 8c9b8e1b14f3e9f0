"""The check fails what it must.

Each run drives the rest of a cell's run (fixture, store, scan loop,
window, check) below the chip gate, with the timed path broken
underneath, and sees `correct` come out false: a verdict altered where it
is produced (every cell), half of each chunk left out and taken as valid
(the corrupt mix: the clean mix's rounds are all valid, so no check of
it can see a verifier that accepts too much), a handle that fails over
off its device during warm-up or in the window, and each cell's control,
the reference with one guarantee of the configuration broken.  The
reference's own anchors hold, and a wrong published answer is counted.
"""

import time

import numpy as np
import pytest

from helpers import tiny_tree

CONTROL = {"tiny_quicknet.scan": "wrong_dst",
           "tiny_loe_default.scan": "unchained",
           "tiny_quicknet.scan_corrupt": "chunk_verdict"}
SEED = 2**31 + 7


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return tiny_tree(tmp_path_factory.mktemp("bench"))


def _run(spec, cell, wrap, seed=SEED, seconds=2.0):
    from harness.cell import run_cell
    return run_cell(spec, cell, seed, seconds, False, time.monotonic(),
                    device=False, verify_wrap=wrap)


def flip_one(verify_batch, _fx):
    def verify(rounds, sigs, prevs=None):
        ok = np.array(verify_batch(rounds, sigs, prevs), dtype=bool)
        ok[len(ok) // 3] = ~ok[len(ok) // 3]
        return ok
    return verify


def half_left_out(verify_batch, _fx):
    def verify(rounds, sigs, prevs=None):
        h = len(rounds) // 2
        ok = verify_batch(rounds[:h], sigs[:h], None if prevs is None
                          else prevs[:h])
        return np.concatenate([np.asarray(ok, bool), np.ones(len(rounds) - h,
                                                              bool)])
    return verify


@pytest.mark.parametrize("cell", sorted(CONTROL))
def test_altered_verdict_is_caught(spec, cell):
    r = _run(spec, cell, flip_one)
    assert not r["correct"]
    assert r["checks"]["verdict_mismatch"]["value"] > 0


def test_half_chunk_left_out_is_caught(spec):
    from harness.fixture import ChainFixture
    cell = "tiny_quicknet.scan_corrupt"
    cfg, tr = spec.config("tiny_quicknet"), spec.traffic("scan_corrupt")
    chunk = cfg["config_overrides"]["sync_chunk"]
    # a seed whose first planted round lies in the second half of its chunk
    seed = next(s for s in range(SEED, SEED + 100)
                if (min(ChainFixture(cfg, tr, s, chunk).corrupt) - 1) % chunk
                >= chunk // 2)
    r = _run(spec, cell, half_left_out, seed=seed)
    assert not r["correct"]
    assert r["checks"]["verdict_mismatch"]["value"] > 0


@pytest.mark.parametrize("cell", sorted(CONTROL))
def test_control_is_not_correct(spec, cell):
    import control
    r = _run(spec, cell, control.make_wrap(CONTROL[cell]))
    assert not r["correct"]
    assert r["checks"]["verdict_mismatch"]["value"] > 0


def test_sound_run_is_correct(spec):
    r = _run(spec, "tiny_quicknet.scan_corrupt", None)
    assert r["correct"], r["checks"]


class FaultsTwice:
    """A backend that raises on its calls `at` and `at + 1` (one strike
    and the retry), so the service fails the handle over to the host."""

    def __init__(self, inner, at: int):
        self.inner, self.at, self.calls = inner, at, 0

    def verify_batch(self, rounds, sigs, prev_sigs=None):
        self.calls += 1
        if self.at <= self.calls <= self.at + 1:
            raise RuntimeError("injected device fault")
        return self.inner.verify_batch(rounds, sigs, prev_sigs)


@pytest.mark.parametrize("at,fell", [(1, True), (3, True), (10**9, False)],
                         ids=["in_warm_up", "in_window", "never"])
def test_failover_off_the_device_is_caught(spec, at, fell):
    """Verdicts stay right (the host fallback answers them), but a run
    that left the timed path is not correct: `fell_back` counts it and
    no round is vouched for."""
    from drand_tpu.crypto import schemes
    from drand_tpu.crypto.hostverify import HostBatchVerifier
    from harness.cell import run_cell
    from harness.fixture import ChainFixture
    cfg = spec.config("tiny_quicknet")
    fx = ChainFixture(cfg, spec.traffic("scan"), SEED,
                      cfg["config_overrides"]["sync_chunk"])
    inner = HostBatchVerifier(schemes.scheme_from_name(cfg["scheme"]),
                              fx.chain.public_key)
    r = run_cell(spec, "tiny_quicknet.scan", SEED, 1.0, False,
                 time.monotonic(), device=False,
                 backend=FaultsTwice(inner, at))
    assert r["checks"]["verdict_mismatch"]["value"] == 0
    assert (r["checks"]["fell_back"]["value"] >= 1) == fell
    assert r["correct"] == (not fell), r["checks"]
    assert (r["failed"] >= r["attempted"] > 0) == fell


def test_reference_anchors_hold():
    from harness import check
    assert check.anchor_mismatch() == 0


def test_wrong_published_answer_is_counted(tmp_path):
    import json
    from harness import check
    with open(check.ANCHORS) as f:
        doc = json.load(f)
    v = doc["hash_to_curve"]["vectors"][0]
    v["point"][0] = v["point"][0][:-1] + "0"
    doc["beacons"]["items"][0]["round"] += 2
    p = tmp_path / "anchors.json"
    p.write_text(json.dumps(doc))
    # the vector's point, and the beacon: it no longer verifies
    assert check.anchor_mismatch(str(p)) == 2
