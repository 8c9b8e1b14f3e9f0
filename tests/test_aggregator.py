"""The aggregation path's host side, on the CPU with no device program:
the daemon's factory for the aggregator's verifier, the localisation of
invalid slots by halves of the partials program's mask (with a stand-in
for the device check), and the spans and counters a recovered round
leaves in the registry.  tests/test_aggregate_reference.py drives the
same path with the device program itself against the plain reference.
"""

import itertools

import numpy as np
import pytest

from drand_tpu.beacon.chainstore import DevicePartialVerifier, \
    HostPartialVerifier
from drand_tpu.core.beacon_process import aggregation_verifier_factory
from drand_tpu.crypto import partials, tbls
from drand_tpu.crypto.schemes import scheme_from_name
from drand_tpu.crypto.verify_service import VerifyService
from harness import BeaconScenario, OwnWork


class StandIn:
    """The partials program's verdict over a mask, from known truth: a
    check passes iff every slot in it is valid.  Counts its passes."""

    def __init__(self, valid):
        self.valid = np.asarray(valid, dtype=bool)
        self.passes = 0

    def __call__(self, ids):
        self.passes += 1
        return bool(self.valid[ids].all()), np.ones(self.valid.size, bool)


def _localise(valid):
    check = StandIn(valid)
    ids = np.arange(len(valid))
    ok, _ = check(ids)
    good = np.zeros(len(valid), dtype=bool)
    if ok:
        good[ids] = True
    else:
        partials._localise(check, ids, True, good)
    return good, check.passes


@pytest.mark.parametrize("n", [1, 2, 6, 7, 10])
def test_localise_finds_exactly_the_invalid_slots(n):
    """Every pattern of up to two invalid slots among n: the result is the
    truth, and one invalid slot costs at most 2·log2(n) + 1 passes."""
    for bad in itertools.chain([()], itertools.combinations(range(n), 1),
                               itertools.combinations(range(n), 2)):
        valid = np.ones(n, dtype=bool)
        valid[list(bad)] = False
        good, passes = _localise(valid)
        assert (good == valid).all(), bad
        if len(bad) == 1:
            assert passes <= 2 * int(np.ceil(np.log2(n))) + 1, (bad, passes)
    good, _ = _localise(np.zeros(n, dtype=bool))
    assert not good.any()


@pytest.mark.parametrize("device", [True, False], ids=["device", "host"])
def test_daemon_factory_puts_the_verifier_on_the_live_lane(device):
    sch = scheme_from_name("pedersen-bls-chained")
    poly = tbls.PriPoly.random(2, secret=31337)
    svc = VerifyService()
    try:
        pv = aggregation_verifier_factory(svc, device)(
            sch, poly.commit(sch.key_group), 3)
        assert isinstance(pv.inner, DevicePartialVerifier if device
                          else HostPartialVerifier)
        # a device verifier falls back to the host one; a host one has
        # nothing to fall back to
        assert (pv._fallback_factory is not None) == device
    finally:
        svc.stop()


def test_a_recovered_round_leaves_its_spans(monkeypatch):
    """Each node's aggregator times its verifier call, recovery, final
    verification and append once per stored round, and each node times
    the signing of its partial (host verifier, two rounds, 3 nodes)."""
    work = OwnWork(monkeypatch)
    sc = BeaconScenario(n=3, thr=2, period=30)
    try:
        sc.start_all()
        sc.advance_to_genesis()
        sc.wait_all(1)
        sc.advance_round()
        sc.wait_all(2)
    finally:
        sc.stop_all()
    d = work.delta()
    for name in ("agg.partials", "agg.recover", "agg.final_verify",
                 "agg.append"):
        assert d[name][0] >= 2 * 3, name
        assert d[name][1] > 0, name
    assert d["node.sign_partial"][0] >= 2 * 3
    assert "partials.invalid" not in d and "partials.fallback" not in d
    assert set(d).isdisjoint({"scan.verify", "scan.outside"})

