"""Live threshold aggregation through the daemon's path against the plain
reference (`benchmark/harness/reftbls.py`, which imports nothing of the
program): a Handler whose aggregator verifies partials with the device
verifier (on the CPU backend here), built by the daemon's own factory,
aggregates a small chained G2 group over a few rounds, one of them with a
planted invalid partial and a late honest one.

The module builds the node and drives its rounds once (the G2 partials
program compiles once); each test checks one promise of it.
"""

import importlib
import os
import sys
import types

import pytest

from drand_tpu import metrics
from drand_tpu.beacon.node import Handler, HandlerConfig, PartialBeaconPacket
from drand_tpu.chain.beacon import Beacon
from drand_tpu.chain.sqlitedb import SqliteStore
from drand_tpu.core.beacon_process import aggregation_verifier_factory
from drand_tpu.crypto import schemes, tbls
from drand_tpu.crypto.verify_service import VerifyService
from drand_tpu.key import DistPublic, Share, new_group, new_keypair

N, T, ME = 4, 3, 0
SCHEME = "pedersen-bls-chained"
GENESIS = 1595431050            # the League of Entropy default chain's
CHECKPOINT = 1000               # the stored round the node resumes from
SEED = 2**31 + 77


def _reftbls():
    """The benchmark's reference, loaded as a package of its own (the
    name `harness` is this directory's test helper)."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "harness")
    pkg = sys.modules.get("bench_harness")
    if pkg is None:
        pkg = types.ModuleType("bench_harness")
        pkg.__path__ = [bench]
        sys.modules["bench_harness"] = pkg
    return importlib.import_module("bench_harness.reftbls")


class Recorder:
    """The aggregator's verifier as the factory built it, every call's
    partials and verdicts kept."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = []

    def verify(self, msg, partials):
        ok = self.inner.verify(msg, partials)
        self.calls.append((bytes(msg), list(partials), [bool(v) for v in ok]))
        return ok


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """Drive rounds CHECKPOINT+1.. through one node; -> what happened.
    The CPU backend compiles the G2 partials program in about half an
    hour, near the verify service's 30 minutes for a compiling dispatch,
    so the service spares it longer here."""
    from drand_tpu.crypto import verify_service
    limit = verify_service.DEFAULT_COMPILE_LIMIT
    verify_service.DEFAULT_COMPILE_LIMIT = 4 * 3600.0
    try:
        return _drive(tmp_path_factory)
    finally:
        verify_service.DEFAULT_COMPILE_LIMIT = limit


def _drive(tmp_path_factory):
    ref = _reftbls()
    dealer = ref.Dealer(SCHEME, N, T, SEED)
    scheme = schemes.scheme_from_name(SCHEME)
    prev0 = bytes(range(96))
    beacons = {CHECKPOINT - 1: prev0}
    beacons[CHECKPOINT] = dealer.beacon(dealer.message(CHECKPOINT, prev0))

    store = SqliteStore(str(tmp_path_factory.mktemp("agg") / "chain.db"),
                        require_previous=True)
    store.put_many([Beacon(round=r, signature=beacons[r])
                    for r in (CHECKPOINT - 1, CHECKPOINT)])
    pairs = [new_keypair(f"node{i}.test:443", scheme, seed=b"agg%d" % i)
             for i in range(N)]
    group = new_group([p.public for p in pairs], T, genesis=GENESIS,
                      period=30, catchup_period=0, scheme=scheme)
    group.public_key = DistPublic(list(dealer.commits))
    share = Share(scheme=scheme, private=tbls.PriShare(ME, dealer.shares[ME]),
                  commits=list(dealer.commits))
    svc = VerifyService()
    factory = aggregation_verifier_factory(svc, True)
    rec = {}

    def recorded(*a):
        rec["log"] = Recorder(factory(*a))
        return rec["log"]

    sent = []
    handler = Handler(HandlerConfig(group=group, share=share, index=ME,
                                    store=store, verifier_factory=recorded,
                                    broadcast=sent.append))
    fell0 = metrics.totals().get("partials.fallback", [0])[0]
    # per round: the peers' partials in arrival order, the node's own
    # being the first; "bad" is peer 1 signing the previous round's
    # message, placed within the first T arrivals, peer 3 arriving late
    plans = [[2, 1], ["bad", 2, 3], [3, 1], [1, 3, 2]]
    planted = set()
    try:
        for i, plan in enumerate(plans):
            r = CHECKPOINT + 1 + i
            last = handler.chain.last()
            handler.broadcast_next_partial(last)
            assert sent[-1].round == r
            msg = dealer.message(r, beacons[r - 1])
            for who in plan:
                p = dealer.partial(1, dealer.message(r - 1, beacons[r - 2])) \
                    if who == "bad" else dealer.partial(who, msg)
                if who == "bad":
                    planted.add((msg, p))
                handler.process_partial_beacon(PartialBeaconPacket(
                    round=r, previous_signature=beacons[r - 1],
                    partial_sig=p))
            # the first round carries the program's first call
            b = handler.chain.wait_for_round(r, 4 * 3600 if i == 0 else 600)
            assert b is not None, f"round {r} was not stored"
            beacons[r] = dealer.beacon(msg)
        stored = {r: handler.chain.store.get(r)
                  for r in range(CHECKPOINT + 1, CHECKPOINT + 1 + len(plans))}
    finally:
        handler.stop()
        svc.stop()
    return {"dealer": dealer, "beacons": beacons, "stored": stored,
            "calls": rec["log"].calls, "planted": planted,
            "fell_back": metrics.totals().get("partials.fallback", [0])[0]
            - fell0}


def test_stored_beacons_are_the_groups_signatures(run):
    for r, b in run["stored"].items():
        assert bytes(b.signature) == run["beacons"][r], r
        assert bytes(b.previous_sig) == run["beacons"][r - 1], r
        assert run["dealer"].verify_beacon(
            run["dealer"].message(r, run["beacons"][r - 1]), b.signature)


def test_every_verdict_is_the_references(run):
    dealer = run["dealer"]
    n = 0
    for msg, partials, verdicts in run["calls"]:
        for p, v in zip(partials, verdicts):
            assert v == dealer.verify_partial(msg, p)
            n += 1
    # own + two peers in each of the four rounds, the late peer of the
    # planted round verified alone, and the planted partial
    assert n == 4 * T + 1


def test_planted_partial_is_judged_invalid_and_never_used(run):
    # the planted bytes are peer 1's valid partial of the round before:
    # judged against the planted round's message, they are invalid
    (bad,) = run["planted"]
    judged = [v for msg, ps, vs in run["calls"] for p, v in zip(ps, vs)
              if (msg, p) == bad]
    assert judged == [False]
    # recovery over the remaining valid partials gave the group's beacon
    r = CHECKPOINT + 2
    assert bytes(run["stored"][r].signature) == run["beacons"][r]


def test_no_call_fell_back_to_the_host(run):
    assert run["fell_back"] == 0


def test_one_partials_program_for_every_round(run):
    firsts = {k: v[0] for k, v in metrics.totals().items()
              if k.startswith("batch.first_call/g2_partials_")
              and k.count("/") == 1}
    assert firsts == {f"batch.first_call/g2_partials_rlc.fields@{N}": 1}
