"""Occupancy campaign (ISSUE 10): depth-k dispatch pipelining, per-handle
lane-width/depth tuning (TUNING.json precedence), and the queue/device
latency split — all against stub backends so tier-1 compiles nothing.

CPU verdict-parity of the real crypto pipelines (depth-1 vs depth-k
streams, narrow vs wide pads, the fused recover) lives in
tests/test_batch.py / tests/test_partials.py — the conftest heavy
bucket — because those compile the pairing programs.
"""

import json
import os
import threading
import types

import numpy as np
import pytest

from drand_tpu.beacon.clock import FakeClock
from drand_tpu.crypto import tuning
from drand_tpu.crypto.verify_service import (DEFAULT_PAD, LANE_LIVE,
                                             VerifyService)

SCHEME = types.SimpleNamespace(id="stub-scheme")
PK = b"\x01" * 48


def stub_rule(round_, sig):
    return sig == b"sig-%d" % round_


def beacons(rng, bad=()):
    rounds = list(rng)
    sigs = [b"sig-%d" % r if r not in bad else b"forged" for r in rounds]
    return rounds, sigs, [None] * len(rounds)


class PipelinedStub:
    """pack/dispatch/resolve triple recorder (no jax)."""

    kind = "stub"
    pad_to = 0

    def __init__(self):
        self.calls = []
        self.stages = []

    def verify_batch(self, rounds, sigs, prev_sigs=None):
        self.calls.append(list(rounds))
        return np.array([stub_rule(r, s) for r, s in zip(rounds, sigs)],
                        dtype=bool)

    def pack_chunk(self, rounds, sigs, prev_sigs=None):
        self.stages.append(("pack", len(rounds)))
        return list(rounds), list(sigs)

    def dispatch_packed(self, packed):
        rounds, sigs = packed
        self.calls.append(list(rounds))
        self.stages.append(("dispatch", len(rounds)))
        return all(stub_rule(r, s) for r, s in zip(rounds, sigs))

    def resolve_packed(self, packed, verdict):
        rounds, sigs = packed
        self.stages.append(("resolve", len(rounds)))
        if verdict:
            return np.ones(len(rounds), dtype=bool)
        return np.array([stub_rule(r, s) for r, s in zip(rounds, sigs)],
                        dtype=bool)


def make_service(**kw):
    kw.setdefault("clock", FakeClock(1000.0))
    kw.setdefault("pad", 8)
    kw.setdefault("background_window", 0.0)
    return VerifyService(**kw)


# -- depth-k pipelined executor ----------------------------------------------


def test_depth_k_keeps_k_dispatches_in_flight():
    """With pipeline_depth=3, the executor enqueues up to 3 chunks ahead
    of the resolve point: the first resolve happens only after 4 chunks
    are dispatched (window full), not after 2 (the old double buffer)."""
    svc = make_service(pad=4, pipeline_depth=3)
    stub = PipelinedStub()
    h = svc.handle(SCHEME, PK, backend=stub)
    ok = h.verify_batch(*beacons(range(1, 21), bad={9}))   # 5 chunks of 4
    assert len(ok) == 20 and not ok[8] and ok.sum() == 19
    kinds = [k for k, _ in stub.stages if k != "pack"]
    assert kinds.index("resolve") == 4, kinds
    assert kinds.count("dispatch") == 5 and kinds.count("resolve") == 5
    st = svc.stats()
    assert st["inflight_depth_max"] == 4   # window + the advancing chunk
    svc.stop()


def test_depth_1_is_the_old_double_buffer():
    svc = make_service(pad=4, pipeline_depth=1)
    stub = PipelinedStub()
    h = svc.handle(SCHEME, PK, backend=stub)
    assert h.verify_batch(*beacons(range(1, 13))).all()    # 3 chunks
    kinds = [k for k, _ in stub.stages if k != "pack"]
    assert kinds == ["dispatch", "dispatch", "resolve", "dispatch",
                     "resolve", "resolve"]
    svc.stop()


def test_depth_parity_stub_verdicts_identical():
    """Same inputs through depth-1 and depth-4 services produce
    bit-identical verdicts (the coalescer/chunker is depth-agnostic)."""
    outs = {}
    for depth in (1, 4):
        svc = make_service(pad=4, pipeline_depth=depth)
        stub = PipelinedStub()
        h = svc.handle(SCHEME, PK, backend=stub)
        outs[depth] = h.verify_batch(*beacons(range(1, 31),
                                              bad={3, 17, 29}))
        svc.stop()
    assert (outs[1] == outs[4]).all()


def test_backend_footprint_cap_clamps_depth():
    """A backend exposing pipeline_depth() (BatchBeaconVerifier's
    VMEM-budget clamp) bounds the service's requested depth."""
    class Capped(PipelinedStub):
        asked = None

        def pipeline_depth(self, depth, pad):
            Capped.asked = (depth, pad)
            return 2

    svc = make_service(pad=4, pipeline_depth=64)
    h = svc.handle(SCHEME, PK, backend=Capped())
    assert h.verify_batch(*beacons(range(1, 25))).all()    # 6 chunks
    assert Capped.asked == (64, 4)
    kinds = [k for k, _ in h.backend.stages if k != "pack"]
    assert kinds.index("resolve") == 3     # window capped at 2, not 64
    svc.stop()


def test_verifier_pipeline_depth_math():
    """The real clamp: depth x per-chunk footprint <= the in-flight
    budget; no device work, just arithmetic on the constructed verifier."""
    from drand_tpu.crypto import batch
    from drand_tpu.crypto.schemes import scheme_from_name

    sch = scheme_from_name("bls-unchained-on-g1")
    _, pub = sch.keypair(seed=b"occupancy-depth")
    ver = batch.BatchBeaconVerifier(sch, sch.public_bytes(pub), pad_to=8192)
    assert ver.pipeline_depth(1, 8192) == 1
    cap = batch.max_pipeline_depth(8192, g2sig=False)
    assert ver.pipeline_depth(10 ** 6, 8192) == cap
    # G2 lanes are ~2x the bytes: same budget, smaller cap
    assert batch.max_pipeline_depth(8192, True) < cap
    assert batch.chunk_footprint_bytes(16384, False) \
        == 2 * batch.chunk_footprint_bytes(8192, False)


# -- watchdog: deadline on the oldest of a shared-device window ---------------


def test_watchdog_deadline_scales_with_inflight_window():
    svc = make_service(watchdog_floor=0.5, watchdog_factor=4.0)
    h = svc.handle(SCHEME, PK, backend=PipelinedStub(),
                   fallback=PipelinedStub())
    slot = svc._slots[h.key]
    slot.latencies.extend([0.1, 0.2, 1.0])
    assert svc._deadline_for(slot) == pytest.approx(4.0)
    # k dispatches share the device: the oldest ticket's budget covers
    # the window
    assert svc._deadline_for(slot, scale=3) == pytest.approx(12.0)
    # the cold-compile floor never scales
    slot.latencies.clear()
    assert svc._deadline_for(slot, scale=8) == 0.5
    svc.stop()


def test_watchdog_trips_only_the_oldest_ticket_per_slot():
    """Two tickets on one slot, both past deadline: only the OLDEST
    trips (younger work is judged once it becomes oldest — k in-flight
    dispatches are one shared-device window, not k independent hangs)."""
    from drand_tpu.crypto.verify_service import _Batch, _Ticket

    svc = make_service(watchdog_floor=5.0)
    h = svc.handle(SCHEME, PK, backend=PipelinedStub(),
                   fallback=PipelinedStub())
    slot = svc._slots[h.key]
    now = svc.clock.monotonic()
    old = _Ticket(slot, _Batch(LANE_LIVE), "chunk", now, now + 1.0)
    young = _Ticket(slot, _Batch(LANE_LIVE), "chunk", now + 0.5, now + 1.5)
    trips = []
    svc._trip = lambda t: trips.append(t)      # observe, don't failover
    with svc._cond:
        # start the watchdog (via the slot's group stream)
        svc._ensure_threads_locked(svc._stream_locked(slot.gid))
        svc._tickets[id(old)] = old
        svc._tickets[id(young)] = young
        svc._cond.notify_all()
    svc.clock.advance(2.0)                     # both past deadline
    deadline = threading.Event()
    for _ in range(100):
        if trips:
            break
        deadline.wait(0.05)
    assert [t is old for t in trips] == [True], trips
    assert not young.cancelled
    svc.stop()


# -- TUNING.json consultation (the autotune acceptance, no compiles) ---------


def _write_tuning(path, platform, kind, pad, depth):
    with open(path, "w") as f:
        json.dump({"version": 1, "entries":
                   {platform: {kind: {"pad": pad, "depth": depth}}}}, f)


def test_service_consults_tuning_file(tmp_path, monkeypatch):
    import jax
    tf = tmp_path / "TUNING.json"
    _write_tuning(tf, jax.default_backend(), "g1", 4, 3)
    monkeypatch.setenv("DRAND_TUNING_FILE", str(tf))
    monkeypatch.delenv("DRAND_VERIFY_PAD", raising=False)
    monkeypatch.delenv("DRAND_VERIFY_PIPELINE_DEPTH", raising=False)
    svc = make_service(pad=0)                  # AUTO: must consult
    stub = PipelinedStub()
    h = svc.handle(SCHEME, PK, backend=stub)
    assert h.verify_batch(*beacons(range(1, 11))).all()
    # the tuned pad drives the chunking: 10 rounds at pad 4 -> 4,4,2
    assert [len(c) for c in stub.calls] == [4, 4, 2]
    tun = next(iter(svc.stats()["tuning"].values()))
    assert (tun["pad"], tun["depth"]) == (4, 3)
    svc.stop()


def test_env_override_beats_tuning_file(tmp_path, monkeypatch):
    import jax
    tf = tmp_path / "TUNING.json"
    _write_tuning(tf, jax.default_backend(), "g1", 4, 3)
    monkeypatch.setenv("DRAND_TUNING_FILE", str(tf))
    monkeypatch.setenv("DRAND_VERIFY_PAD", "6")
    monkeypatch.setenv("DRAND_VERIFY_PIPELINE_DEPTH", "2")
    svc = make_service(pad=0)
    stub = PipelinedStub()
    h = svc.handle(SCHEME, PK, backend=stub)
    assert h.verify_batch(*beacons(range(1, 11))).all()
    assert [len(c) for c in stub.calls] == [6, 4]
    tun = next(iter(svc.stats()["tuning"].values()))
    assert (tun["pad"], tun["depth"]) == (6, 2)
    svc.stop()


def test_explicit_ctor_pad_pins_over_everything(tmp_path, monkeypatch):
    import jax
    tf = tmp_path / "TUNING.json"
    _write_tuning(tf, jax.default_backend(), "g1", 4, 3)
    monkeypatch.setenv("DRAND_TUNING_FILE", str(tf))
    monkeypatch.setenv("DRAND_VERIFY_PAD", "6")
    svc = make_service(pad=8, pipeline_depth=1)
    stub = PipelinedStub()
    h = svc.handle(SCHEME, PK, backend=stub)
    assert h.verify_batch(*beacons(range(1, 11))).all()
    assert [len(c) for c in stub.calls] == [8, 2]
    svc.stop()


def test_no_file_no_env_is_todays_default(monkeypatch):
    monkeypatch.delenv("DRAND_TUNING_FILE", raising=False)
    monkeypatch.delenv("DRAND_VERIFY_PAD", raising=False)
    monkeypatch.delenv("DRAND_VERIFY_PIPELINE_DEPTH", raising=False)
    monkeypatch.chdir("/tmp")                  # no cwd TUNING.json
    pad, depth, src, pinned = tuning.resolve("g2", "cpu")
    assert (pad, depth) == (DEFAULT_PAD, 1)
    assert src == "pad:default,depth:default"
    assert not pinned


def test_tuning_resolve_platform_scoped(tmp_path, monkeypatch):
    """A chip sweep's numbers never apply to another platform."""
    tf = tmp_path / "TUNING.json"
    _write_tuning(tf, "tpu", "g2", 32768, 4)
    monkeypatch.setenv("DRAND_TUNING_FILE", str(tf))
    monkeypatch.delenv("DRAND_VERIFY_PAD", raising=False)
    monkeypatch.delenv("DRAND_VERIFY_PIPELINE_DEPTH", raising=False)
    assert tuning.resolve("g2", "tpu")[:2] == (32768, 4)
    assert tuning.resolve("g2", "cpu")[:2] == (DEFAULT_PAD, 1)
    assert tuning.resolve("g1", "tpu")[:2] == (DEFAULT_PAD, 1)


def test_tuning_malformed_file_is_ignored(tmp_path, monkeypatch):
    tf = tmp_path / "TUNING.json"
    tf.write_text("{not json")
    monkeypatch.setenv("DRAND_TUNING_FILE", str(tf))
    monkeypatch.delenv("DRAND_VERIFY_PAD", raising=False)
    monkeypatch.delenv("DRAND_VERIFY_PIPELINE_DEPTH", raising=False)
    assert tuning.resolve("g1", "cpu")[:2] == (DEFAULT_PAD, 1)


def test_tuning_resolve_keyed_by_group_size(tmp_path, monkeypatch):
    """ISSUE 11: a `<kind>@<n>` entry is the n-device-group winner and
    beats the bare kind; sizes with no sweep fall back to the bare kind
    (the legacy 1-device spelling)."""
    tf = tmp_path / "TUNING.json"
    with open(tf, "w") as f:
        json.dump({"version": 1, "entries": {"cpu": {
            "g1": {"pad": 64, "depth": 1},
            "g1@4": {"pad": 256, "depth": 2}}}}, f)
    monkeypatch.setenv("DRAND_TUNING_FILE", str(tf))
    monkeypatch.delenv("DRAND_VERIFY_PAD", raising=False)
    monkeypatch.delenv("DRAND_VERIFY_PIPELINE_DEPTH", raising=False)
    assert tuning.resolve("g1", "cpu", group_size=1)[:2] == (64, 1)
    assert tuning.resolve("g1", "cpu", group_size=4)[:2] == (256, 2)
    # no @2 sweep: the bare-kind fallback serves
    assert tuning.resolve("g1", "cpu", group_size=2)[:2] == (64, 1)
    # a different-platform @4 entry never leaks
    assert tuning.resolve("g1", "tpu", group_size=4)[:2] \
        == (DEFAULT_PAD, 1)


def test_service_resolves_tuning_for_its_group_size(tmp_path, monkeypatch):
    """A handle whose device group owns 2 devices resolves the g1@2
    winner, not the 1-device entry."""
    import jax
    tf = tmp_path / "TUNING.json"
    with open(tf, "w") as f:
        json.dump({"version": 1, "entries": {jax.default_backend(): {
            "g1": {"pad": 4, "depth": 1},
            "g1@2": {"pad": 6, "depth": 2}}}}, f)
    monkeypatch.setenv("DRAND_TUNING_FILE", str(tf))
    monkeypatch.delenv("DRAND_VERIFY_PAD", raising=False)
    monkeypatch.delenv("DRAND_VERIFY_PIPELINE_DEPTH", raising=False)
    svc = make_service(pad=0, device_groups=4)     # 8 devices -> 2 each
    stub = PipelinedStub()
    h = svc.handle(SCHEME, PK, backend=stub)
    assert h.verify_batch(*beacons(range(1, 11))).all()
    assert [len(c) for c in stub.calls] == [6, 4]  # the @2 pad drives
    tun = next(iter(svc.stats()["tuning"].values()))
    assert (tun["pad"], tun["depth"]) == (6, 2)
    svc.stop()


def test_write_tuning_merges_platforms(tmp_path):
    tf = str(tmp_path / "TUNING.json")
    tuning.write_tuning(tf, "cpu", {"g1": {"pad": 64, "depth": 1}})
    tuning.write_tuning(tf, "tpu", {"g2": {"pad": 32768, "depth": 4}})
    ent = tuning.load_entries(tf)
    assert ent["cpu"]["g1"]["pad"] == 64
    assert ent["tpu"]["g2"]["depth"] == 4


# -- the dispatch-latency split ----------------------------------------------


def test_stats_carry_queue_device_split_and_summary():
    svc = make_service(pad=4, background_window=100.0)
    stub = PipelinedStub()
    h = svc.handle(SCHEME, PK, backend=stub)
    f = h.submit(*beacons([1, 2]))
    svc.clock.advance(101.0)                   # window expiry = queue time
    assert f.result(10).all()
    st = svc.stats()
    assert st["queue_time_s"] >= 100.0         # the fake-clock window wait
    assert st["device_time_s"] >= 0.0
    assert st["pack_time_s"] >= 0.0            # the ISSUE 14 pack term
    assert "inflight_depth_max" in st
    s = svc.summary()
    assert "inflight<=" in s and "pt/qt/dt=" in s
    svc.stop()


def test_health_payload_carries_occupancy_fields():
    """/health surfaces the inflight gauge + latency split (the fields,
    not a daemon e2e — that path is covered by test_daemon_e2e)."""
    svc = make_service(pad=4)
    h = svc.handle(SCHEME, PK, backend=PipelinedStub())
    assert h.verify_batch(*beacons([1])).all()
    st = svc.stats()
    payload = {"verify_inflight_depth": st["inflight_depth_max"],
               "verify_latency_split": {"pack_s": st["pack_time_s"],
                                        "queue_s": st["queue_time_s"],
                                        "device_s": st["device_time_s"]}}
    assert set(payload["verify_latency_split"]) == \
        {"pack_s", "queue_s", "device_s"}
    svc.stop()


def test_metrics_series_exist():
    from drand_tpu import metrics
    metrics.verify_inflight.set(3)
    metrics.verify_dispatch_latency.labels("live", "queue").observe(0.1)
    metrics.verify_dispatch_latency.labels("live", "device").observe(0.2)
    metrics.verify_dispatch_latency.labels("live", "pack").observe(0.05)
    blob = metrics.scrape("private").decode()
    assert "verify_service_inflight_depth 3.0" in blob
    assert 'verify_service_dispatch_latency_seconds_count{lane="live",phase="queue"}' in blob
    assert 'verify_service_dispatch_latency_seconds_count{lane="live",phase="pack"}' in blob
