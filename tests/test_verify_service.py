"""Resident verify service (crypto/verify_service.py): coalescer,
priority lanes, deadline flush, future fan-out, preemption, and the
dispatch-count acceptance criterion.

All scheduler tests run against injected stub backends (no jax, no
device): the service is backend-agnostic by design, and the stub records
exactly the dispatches the device would have seen.  One test pins the
fan-out verdicts against the real `HostBatchVerifier`."""

import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from drand_tpu.beacon.clock import FakeClock
from drand_tpu.crypto.verify_service import (LANE_BACKGROUND, LANE_LIVE,
                                             VerifyService, current_service,
                                             get_service, set_service)

SCHEME = types.SimpleNamespace(id="stub-scheme")
PK = b"\x01" * 48


def stub_rule(round_, sig):
    """Deterministic per-round verdict: sig must be the round's tag."""
    return sig == b"sig-%d" % round_


class StubBackend:
    """Records every dispatch; verdicts via stub_rule.  `gate` (if set)
    blocks the FIRST dispatch until released, so tests can deterministically
    interleave live submissions with an in-flight background batch."""

    kind = "stub"

    def __init__(self, gate=None):
        self.calls = []
        self.gate = gate
        self.started = threading.Event()

    def verify_batch(self, rounds, sigs, prev_sigs=None):
        first = not self.calls
        self.calls.append(list(rounds))
        self.started.set()
        if self.gate is not None and first:
            assert self.gate.wait(10), "test gate never released"
        return np.array([stub_rule(r, s) for r, s in zip(rounds, sigs)],
                        dtype=bool)


class PipelinedStub(StubBackend):
    """Stub exposing the pack/dispatch/resolve triple so the service's
    double-buffered device path is exercised without jax."""

    pad_to = 0

    def __init__(self):
        super().__init__()
        self.stages = []

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


def beacons(rng, bad=()):
    rounds = list(rng)
    sigs = [b"sig-%d" % r if r not in bad else b"forged" for r in rounds]
    return rounds, sigs, [None] * len(rounds)


def make_service(**kw):
    kw.setdefault("clock", FakeClock(1000.0))
    kw.setdefault("pad", 8)
    kw.setdefault("background_window", 0.0)
    return VerifyService(**kw)


# -- coalescer ----------------------------------------------------------------


def test_coalesces_concurrent_submissions_into_one_dispatch():
    svc = make_service(background_window=100.0)
    stub = StubBackend()
    h = svc.handle(SCHEME, PK, backend=stub)
    futs = [h.submit(*beacons(range(i * 2 + 1, i * 2 + 3))) for i in range(3)]
    # nothing flushes inside the coalescing window with the batch unfilled
    assert not any(f.done() for f in futs)
    svc.clock.advance(101.0)
    outs = [f.result(timeout=10) for f in futs]
    assert all(o.all() for o in outs)
    assert len(stub.calls) == 1             # ONE dispatch for all three
    assert sorted(stub.calls[0]) == list(range(1, 7))
    assert svc.stats()["dispatches"] == 1
    svc.stop()


def test_full_batch_flushes_before_window():
    svc = make_service(pad=4, background_window=1e6)
    stub = StubBackend()
    h = svc.handle(SCHEME, PK, backend=stub)
    f = h.submit(*beacons(range(1, 5)))     # fills the pad exactly
    assert f.result(timeout=10).all()       # no clock advance needed
    svc.stop()


def test_oversize_submission_is_chunked_at_pad():
    svc = make_service(pad=8)
    stub = StubBackend()
    h = svc.handle(SCHEME, PK, backend=stub)
    ok = h.verify_batch(*beacons(range(1, 21), bad={7, 19}))
    assert len(ok) == 20
    assert not ok[6] and not ok[18]
    assert ok.sum() == 18
    assert [len(c) for c in stub.calls] == [8, 8, 4]
    svc.stop()


def test_flush_on_deadline_with_fake_clock():
    svc = make_service(background_window=50.0)
    stub = StubBackend()
    h = svc.handle(SCHEME, PK, backend=stub)
    f = h.submit(*beacons([1]))
    assert not f.done()
    svc.clock.advance(49.0)
    assert not f.done()
    svc.clock.advance(2.0)                  # window expired: flush
    assert f.result(timeout=10).all()
    svc.stop()


def test_blocking_verify_batch_skips_the_window():
    """A blocking caller (catch-up sync's serial chunk loop) cannot feed
    the coalescer while it waits, so verify_batch flushes immediately
    even with a huge window / frozen fake clock — but already-queued
    same-chain async work still rides the dispatch."""
    svc = make_service(background_window=1e6)
    stub = StubBackend()
    h = svc.handle(SCHEME, PK, backend=stub)
    rider = h.submit(*beacons([50]))        # async: parked on the window
    assert not rider.done()
    ok = h.verify_batch(*beacons([1, 2]))   # no clock advance needed
    assert ok.all()
    assert rider.result(10).all()           # coalesced into the flush
    assert len(stub.calls) == 1
    assert sorted(stub.calls[0]) == [1, 2, 50]
    svc.stop()


def test_live_lane_skips_the_coalescing_window():
    svc = make_service(background_window=1e6)
    stub = StubBackend()
    h = svc.handle(SCHEME, PK, backend=stub)
    f = h.submit(*beacons([1]), lane=LANE_LIVE)
    assert f.result(timeout=10).all()       # no clock advance needed
    svc.stop()


def test_fanout_slices_match_requests():
    svc = make_service(background_window=100.0)
    stub = StubBackend()
    h = svc.handle(SCHEME, PK, backend=stub)
    f1 = h.submit(*beacons([1, 2, 3], bad={2}))
    f2 = h.submit(*beacons([10, 11]))
    f3 = h.submit(*beacons([20], bad={20}))
    svc.clock.advance(101.0)
    assert f1.result(10).tolist() == [True, False, True]
    assert f2.result(10).tolist() == [True, True]
    assert f3.result(10).tolist() == [False]
    assert len(stub.calls) == 1
    svc.stop()


def test_empty_submission_resolves_immediately():
    svc = make_service()
    h = svc.handle(SCHEME, PK, backend=StubBackend())
    assert h.verify_batch([], []).shape == (0,)
    svc.stop()


def test_distinct_chains_do_not_merge():
    svc = make_service(background_window=100.0)
    s1, s2 = StubBackend(), StubBackend()
    h1 = svc.handle(SCHEME, PK, backend=s1)
    h2 = svc.handle(SCHEME, b"\x02" * 48, backend=s2)
    f1 = h1.submit(*beacons([1, 2]))
    f2 = h2.submit(*beacons([3, 4]))
    svc.clock.advance(101.0)
    assert f1.result(10).all() and f2.result(10).all()
    assert s1.calls == [[1, 2]] and s2.calls == [[3, 4]]
    svc.stop()


# -- double-buffered device path ----------------------------------------------


def test_pipelined_backend_runs_pack_dispatch_resolve():
    svc = make_service(pad=8)
    stub = PipelinedStub()
    h = svc.handle(SCHEME, PK, backend=stub)
    ok = h.verify_batch(*beacons(range(1, 21), bad={5}))
    assert len(ok) == 20 and not ok[4] and ok.sum() == 19
    assert [len(c) for c in stub.calls] == [8, 8, 4]
    kinds = [k for k, _ in stub.stages]
    assert kinds.count("pack") == 3
    # pack timing races the service thread (that's the point of the double
    # buffer), but dispatch/resolve order is deterministic: chunk 1 only
    # resolves AFTER chunk 2 is already dispatched
    assert [k for k in kinds if k != "pack"] == [
        "dispatch", "dispatch", "resolve", "dispatch", "resolve", "resolve"]
    svc.stop()


# -- priority lanes / preemption ----------------------------------------------


def test_live_preempts_background_at_chunk_boundary():
    gate = threading.Event()
    stub = StubBackend(gate=gate)
    svc = make_service(pad=4)
    h = svc.handle(SCHEME, PK, backend=stub)
    order = []

    bg = h.submit(*beacons(range(1, 13)))   # 3 chunks of 4
    assert stub.started.wait(10)            # chunk 1 is on the "device"
    live_call = svc.submit_call(lambda: order.append("live-call") or True,
                                lane=LANE_LIVE)
    live_batch = h.submit(*beacons([100]), lane=LANE_LIVE)
    gate.set()                              # let chunk 1 finish
    assert live_call.result(10) is True
    assert live_batch.result(10).all()
    assert bg.result(10).all()
    # the live work ran BETWEEN background chunks, not after them all
    live_pos = stub.calls.index([100])
    assert 0 < live_pos < len(stub.calls) - 1
    assert svc.stats()["preemptions"] >= 1
    svc.stop()


def test_chaos_background_scan_and_live_partials_contend():
    """A background integrity-scan stream and live partial-aggregation
    calls contend for the service; verdicts stay correct, every future
    resolves, and live work is never starved behind the whole scan."""
    gate = threading.Event()
    stub = StubBackend(gate=gate)
    # ONE device group: the contention this test exercises only exists
    # inside a single dispatch stream — with k groups the live calls
    # round-robin onto sibling streams instead (test_multidevice covers
    # that concurrency)
    svc = make_service(pad=8, device_groups=1)
    h = svc.handle(SCHEME, PK, backend=stub)

    scan_futs = [h.submit(*beacons(range(100 * i, 100 * i + 24), bad={100 * i}))
                 for i in range(4)]         # 96 rounds -> 12 chunks
    assert stub.started.wait(10)
    live_done = []
    partial = svc.partials_factory(
        lambda scheme, poly, n: types.SimpleNamespace(
            verify=lambda msg, ps: live_done.append(len(ps)) or
            [True] * len(ps)))(SCHEME, None, 3)
    results = []
    threads = [threading.Thread(
        target=lambda: results.append(partial.verify(b"m", [b"p1", b"p2"])))
        for _ in range(3)]
    for t in threads:
        t.start()
    gate.set()
    for t in threads:
        t.join(timeout=20)
    assert not any(t.is_alive() for t in threads)
    assert results == [[True, True]] * 3 and live_done == [2, 2, 2]
    for i, f in enumerate(scan_futs):
        ok = f.result(20)
        assert len(ok) == 24 and not ok[0] and ok.sum() == 23
    st = svc.stats()
    assert st["preemptions"] >= 1
    # live calls ran before the final background chunk
    total_calls = len(stub.calls)
    assert total_calls >= 12
    svc.stop()


# -- the dispatch-count acceptance criterion ----------------------------------


def test_mixed_workload_fewer_dispatches_than_per_consumer_baseline():
    """ISSUE 6 acceptance: integrity scan + simulated live partials +
    client verifies through the service issue measurably fewer dispatches
    than the per-consumer baseline (one dispatch per submission), with
    identical verdicts."""
    svc = make_service(pad=64, background_window=100.0)
    stub = StubBackend()
    h = svc.handle(SCHEME, PK, backend=stub)

    workload = []       # (rounds, sigs, prevs) per submission
    # integrity scan: 4 chunks of 16
    for i in range(4):
        workload.append(beacons(range(i * 16 + 1, i * 16 + 17),
                                bad={i * 16 + 3}))
    # client verifies: 6 small sweeps
    for i in range(6):
        workload.append(beacons([200 + i, 300 + i]))
    baseline_dispatches = len(workload)     # the old world: one each
    baseline_verdicts = [np.array([stub_rule(r, s)
                                   for r, s in zip(w[0], w[1])])
                         for w in workload]

    futs = [h.submit(*w) for w in workload]
    # live partials ride along (counted as dispatches in both worlds)
    calls = [svc.submit_call(lambda: True, lane=LANE_LIVE)
             for _ in range(3)]
    baseline_dispatches += 3
    svc.clock.advance(101.0)
    verdicts = [f.result(10) for f in futs]
    assert all(c.result(10) is True for c in calls)

    for got, want in zip(verdicts, baseline_verdicts):
        assert (got == want).all()
    st = svc.stats()
    assert st["dispatches"] < baseline_dispatches, (st, baseline_dispatches)
    # 76 background lanes at pad 64 is 2 coalesced dispatches + 3 calls
    assert st["dispatches"] <= 6
    assert st["submitted"] == 13
    svc.stop()


# -- fan-out vs the host verifier (real crypto) -------------------------------


def test_service_host_handle_matches_host_batch_verifier():
    from drand_tpu.crypto.hostverify import HostBatchVerifier
    from drand_tpu.crypto.schemes import scheme_from_name

    scheme = scheme_from_name("pedersen-bls-chained")
    sec, pub = scheme.keypair(seed=b"verify-service-test")
    pk = scheme.public_bytes(pub)
    rounds, sigs, prevs = [], [], []
    prev = b"\x42" * 32
    for r in range(1, 9):
        sig = scheme.sign(sec, scheme.digest_beacon(r, prev))
        rounds.append(r)
        sigs.append(sig)
        prevs.append(prev)
        prev = sig
    sigs[4] = sigs[3]                       # corrupt round 5

    svc = make_service(background_window=100.0)
    h = svc.handle(scheme, pk, device=False)
    assert h.kind == "host"
    f1 = h.submit(rounds[:3], sigs[:3], prevs[:3])
    f2 = h.submit(rounds[3:], sigs[3:], prevs[3:])
    svc.clock.advance(101.0)
    got = np.concatenate([f1.result(30), f2.result(30)])
    want = HostBatchVerifier(scheme, pk).verify_batch(rounds, sigs, prevs)
    assert (got == want).all()
    assert not got[4] and got.sum() == 7
    svc.stop()


# -- lifecycle / singleton ----------------------------------------------------


def test_stop_fails_pending_futures_and_rejects_new_work():
    svc = make_service(background_window=1e6)
    h = svc.handle(SCHEME, PK, backend=StubBackend())
    f = h.submit(*beacons([1]))
    svc.stop()
    with pytest.raises(RuntimeError):
        f.result(10)
    f2 = h.submit(*beacons([2]))
    with pytest.raises(RuntimeError):
        f2.result(10)


def test_singleton_install_and_clear():
    old = set_service(None)
    try:
        assert current_service() is None
        svc = get_service()
        assert get_service() is svc         # created once
        assert current_service() is svc
        summary = svc.summary()
        assert "dispatches=" in summary and "queue=" in summary
    finally:
        got = set_service(old)
        if got is not None and got is not old:
            got.stop()


def test_backend_exception_propagates_to_all_riders():
    class Boom(StubBackend):
        def verify_batch(self, rounds, sigs, prev_sigs=None):
            raise ValueError("device on fire")

    svc = make_service(background_window=100.0)
    h = svc.handle(SCHEME, PK, backend=Boom())
    f1 = h.submit(*beacons([1]))
    f2 = h.submit(*beacons([2]))
    svc.clock.advance(101.0)
    for f in (f1, f2):
        with pytest.raises(ValueError):
            f.result(10)
    svc.stop()


# -- service-owned sharding (CPU mesh) ----------------------------------------


def test_device_backend_gets_group_placement_and_pool_sharding():
    """A device handle's backend is PINNED to its device group (1 of the
    8 virtual CPU devices under the AUTO one-group-per-device layout),
    while the pool-wide sharded backend spans every device — the
    promotion of __graft_entry__.dryrun_multichip's placement to the
    serving path, now per ISSUE 11.  device_put only; no program
    compiles."""
    jax = pytest.importorskip("jax")
    from drand_tpu.crypto.device_pool import jax_devices
    if len(jax_devices()) < 2:
        pytest.skip("needs a multi-device (virtual CPU) mesh")
    from drand_tpu.crypto.schemes import scheme_from_name

    scheme = scheme_from_name("pedersen-bls-chained")
    _, pub = scheme.keypair(seed=b"shard-test")
    pk = scheme.public_bytes(pub)
    svc = make_service(pad=512)
    h = svc.handle(scheme, pk, device=True)
    assert h.kind == "device"
    ver = h.backend
    assert ver.pad_to == 512
    # group placement: exactly the group's one device
    group = svc._pool.group(h.gid)
    assert group.n_devices == 1
    arr = jax.numpy.asarray(np.zeros((512, 24), np.uint32))
    placed = ver._shard_round_axis((arr,))[0]
    assert placed.sharding.device_set == set(group.devices)
    # a second handle for the same chain is the SAME handle
    h2 = svc.handle(scheme, pk, device=True)
    assert h2 is h
    # the pool-wide sharded backend spans the FULL pool
    slot = svc._slots[h.key]
    assert svc._ensure_pool_backend(slot)
    pool_ver = slot.pool_backend
    assert pool_ver.pad_to == 512 * len(jax_devices())
    wide = jax.numpy.asarray(np.zeros((pool_ver.pad_to, 24), np.uint32))
    placed = pool_ver._shard_round_axis((wide,))[0]
    assert dict(placed.sharding.mesh.shape)["round"] == len(jax_devices())
    svc.stop()


# -- the device failure domain ------------------------------------------------
# watchdog deadlines, retry-once + atomic failover, requeue-not-fail,
# canary re-promotion, per-chunk error containment (ISSUE 7)


import time  # noqa: E402  (test code; real-time waits on service threads)


class FlakyBackend(StubBackend):
    """Raises on every dispatch until `healed` is set."""

    def __init__(self):
        super().__init__()
        self.healed = threading.Event()
        self.attempts = 0

    def verify_batch(self, rounds, sigs, prev_sigs=None):
        self.attempts += 1
        if not self.healed.is_set():
            raise ConnectionError("device unreachable")
        return super().verify_batch(rounds, sigs, prev_sigs)


def test_failing_chunk_contained_to_its_callers():
    """The r7 containment regression: two coalesced callers, one poisoned
    chunk — only the overlapping caller sees the exception, the other
    rider gets its verdicts (no fallback configured here, so the error
    surfaces instead of failing over)."""
    class PoisonChunk(StubBackend):
        def verify_batch(self, rounds, sigs, prev_sigs=None):
            if 3 in rounds:
                raise ValueError("poisoned chunk")
            return super().verify_batch(rounds, sigs, prev_sigs)

    svc = make_service(pad=4, background_window=100.0)
    h = svc.handle(SCHEME, PK, backend=PoisonChunk())
    f1 = h.submit(*beacons([1, 2, 3, 4]))       # fills (poisoned) chunk 1
    f2 = h.submit(*beacons([10, 11]))           # rides in clean chunk 2
    svc.clock.advance(101.0)
    with pytest.raises(ValueError):
        f1.result(10)
    assert f2.result(10).tolist() == [True, True]
    svc.stop()


def test_raise_failover_swaps_to_fallback_and_requeues():
    """raise-on-dispatch: one strike (suspect) + one retry, then the
    backend is swapped to the fallback and the requests REQUEUED — the
    blocking caller resolves with correct verdicts, no exception."""
    svc = make_service(pad=8)
    dev, fb = FlakyBackend(), StubBackend()
    h = svc.handle(SCHEME, PK, backend=dev, fallback=fb)
    ok = h.verify_batch(*beacons([1, 2, 3], bad={2}))
    assert ok.tolist() == [True, False, True]
    assert dev.attempts == 2                    # original + the one retry
    assert fb.calls == [[1, 2, 3]]
    st = svc.stats()
    assert st["failovers"] == 1
    assert list(st["backends"].values()) == ["degraded"]
    assert "DEGRADED" in svc.summary()
    svc.stop()


def test_wrong_shape_result_is_a_fault_and_fails_over():
    """A poisoned device that ANSWERS with a wrong-shape verdict is a
    backend fault, not a caller error."""
    class Poisoned(StubBackend):
        def verify_batch(self, rounds, sigs, prev_sigs=None):
            return super().verify_batch(rounds, sigs, prev_sigs)[:-1]

    svc = make_service(pad=8)
    fb = StubBackend()
    h = svc.handle(SCHEME, PK, backend=Poisoned(), fallback=fb)
    ok = h.verify_batch(*beacons([1, 2, 3]))
    assert ok.all()
    assert fb.calls == [[1, 2, 3]]
    assert svc.stats()["failovers"] == 1
    svc.stop()


def test_watchdog_abandons_hung_dispatch_and_fails_over():
    """hang-forever: the first trip marks the backend suspect and
    requeues on the device (the retry), the second trip degrades to the
    fallback — the caller's future resolves, never an exception, and the
    wedged dispatch threads are abandoned, not waited on."""
    class HangingBackend(StubBackend):
        def __init__(self):
            super().__init__()
            self.release = threading.Event()
            self.hangs = 0

        def verify_batch(self, rounds, sigs, prev_sigs=None):
            self.hangs += 1
            self.started.set()
            self.release.wait(30)
            raise ConnectionError("hung dispatch released")

    svc = make_service(pad=8, watchdog_floor=10.0)
    dev, fb = HangingBackend(), StubBackend()
    h = svc.handle(SCHEME, PK, backend=dev, fallback=fb)
    f = h.submit(*beacons([1, 2]), lane=LANE_LIVE)
    assert dev.started.wait(10)
    svc.clock.advance(11.0)         # trip 1: suspect, retry on the device
    deadline = time.monotonic() + 10
    while dev.hangs < 2 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert dev.hangs == 2
    svc.clock.advance(11.0)         # trip 2: degrade, requeue on fallback
    assert f.result(10).tolist() == [True, True]
    assert fb.calls == [[1, 2]]
    st = svc.stats()
    assert st["watchdog_trips"] == 2
    assert st["failovers"] == 1
    dev.release.set()               # free the abandoned dispatch threads
    svc.stop()


def _blocking_program(release):
    """A jitted program whose trace — inside its first call — waits on
    `release`: a stand-in for a multi-minute cold compile."""
    def f(x):
        release.wait(30)
        return x + 1
    return jax.jit(f)


def test_run_program_marks_only_a_first_call_compiling():
    from drand_tpu.crypto.batch import run_program
    from drand_tpu.crypto.device_pool import thread_compiling
    ev = threading.Event()
    prog = _blocking_program(ev)
    th = threading.Thread(target=run_program, args=(prog, jnp.ones(4)))
    th.start()
    try:
        deadline = time.monotonic() + 10
        while not thread_compiling(th.ident) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert thread_compiling(th.ident)
        assert not thread_compiling(threading.get_ident())
    finally:
        ev.set()
        th.join()
    assert not thread_compiling(th.ident)
    ev.clear()              # a second trace would now block for 30 s
    t0 = time.monotonic()
    assert run_program(prog, jnp.ones(4)).tolist() == [2.0] * 4
    assert time.monotonic() - t0 < 10


class CompilingBackend(StubBackend):
    """verify_batch first runs a program whose compile waits on
    `release`."""

    def __init__(self):
        super().__init__()
        self.release = threading.Event()
        self.prog = _blocking_program(self.release)

    def verify_batch(self, rounds, sigs, prev_sigs=None):
        from drand_tpu.crypto.batch import run_program
        self.started.set()
        run_program(self.prog, jnp.ones(len(rounds)))
        return super().verify_batch(rounds, sigs, prev_sigs)


def test_watchdog_spares_a_compiling_dispatch():
    """A cold compile is minutes of host work on the chip's machine: a
    dispatch still compiling past its deadline is not hung — no trip, no
    failover, the device answers once its program is ready."""
    svc = make_service(pad=8, watchdog_floor=10.0)
    dev, fb = CompilingBackend(), StubBackend()
    h = svc.handle(SCHEME, PK, backend=dev, fallback=fb)
    f = h.submit(*beacons([1, 2]), lane=LANE_LIVE)
    assert dev.started.wait(10)
    for _ in range(3):
        svc.clock.advance(11.0)
        time.sleep(0.2)             # several watchdog polls
    assert svc.stats()["watchdog_trips"] == 0
    dev.release.set()
    assert f.result(10).tolist() == [True, True]
    st = svc.stats()
    assert fb.calls == [] and st["failovers"] == 0
    assert st["backends"] == {svc._slots[h.key].label: "healthy"}
    svc.stop()


def test_watchdog_trips_a_compile_past_the_compile_limit(monkeypatch):
    """A compile that never ends is a hang: past COMPILE_LIMIT from the
    dispatch's start the watchdog trips it like any other stall."""
    from drand_tpu.crypto import verify_service as VS
    monkeypatch.setattr(VS, "DEFAULT_COMPILE_LIMIT", 25.0)
    svc = make_service(pad=8, watchdog_floor=10.0)
    dev, fb = CompilingBackend(), StubBackend()
    h = svc.handle(SCHEME, PK, backend=dev, fallback=fb)
    f = h.submit(*beacons([1, 2]), lane=LANE_LIVE)
    assert dev.started.wait(10)
    try:
        for _ in range(6):
            svc.clock.advance(11.0)
            time.sleep(0.2)
        assert svc.stats()["watchdog_trips"] >= 1
    finally:
        dev.release.set()
    assert f.result(10).tolist() == [True, True]
    svc.stop()


def test_stats_name_the_platform_each_handle_runs_on():
    """A "device" handle on a host with no chip runs on the CPU — the
    stats say so, and injected/host handles say what they are."""
    from drand_tpu.crypto import schemes
    svc = make_service()
    sch = schemes.scheme_from_name(schemes.SHORT_SIG_SCHEME_ID)
    pubs = [sch.public_bytes(sch.keypair(seed=s)[1])
            for s in (b"platform-dev", b"platform-host")]
    dev = svc.handle(sch, pubs[0], device=True)
    host = svc.handle(sch, pubs[1], device=False)
    custom = svc.handle(SCHEME, PK, backend=StubBackend())
    plats = svc.stats()["platforms"]
    label = lambda h: svc._slots[h.key].label  # noqa: E731
    assert plats[label(dev)] == jax.devices()[0].platform == "cpu"
    assert plats[label(host)] == "host"
    assert plats[label(custom)] == "custom"
    svc.stop()


def test_watchdog_deadline_derives_from_latency_history():
    svc = make_service(watchdog_floor=0.5, watchdog_factor=4.0)
    h = svc.handle(SCHEME, PK, backend=StubBackend(), fallback=StubBackend())
    slot = svc._slots[h.key]
    assert svc._deadline_for(slot) == 0.5       # no history: the floor
    slot.latencies.extend([0.1, 0.2, 1.0])
    assert svc._deadline_for(slot) == pytest.approx(4.0)   # factor * p99
    slot.latencies.clear()
    slot.latencies.extend([0.01] * 50)
    assert svc._deadline_for(slot) == 0.5       # floor covers cold compiles
    svc.stop()


def test_probe_repromotes_after_recovery():
    svc = make_service(pad=8, probe_interval=5.0)
    dev, fb = FlakyBackend(), StubBackend()
    h = svc.handle(SCHEME, PK, backend=dev, fallback=fb)
    dev.healed.set()
    assert h.verify_batch(*beacons([1, 2])).all()   # healthy; sample stashed
    dev.healed.clear()
    assert h.verify_batch(*beacons([3, 4])).all()   # fails over
    slot = svc._slots[h.key]
    assert slot.state == "degraded"
    dev.healed.set()                                # the device is back
    # advance the fake clock INSIDE the wait loop (the chaos-scenario
    # pattern): a single up-front advance races the probe thread
    # computing its wait target, parking it on the 60 s real cap
    deadline = time.monotonic() + 10
    while slot.state != "healthy" and time.monotonic() < deadline:
        svc.clock.advance(svc.probe_interval + 1.0)
        time.sleep(0.02)
    assert slot.state == "healthy"
    before = len(dev.calls)
    assert h.verify_batch(*beacons([5])).all()
    assert len(dev.calls) > before                  # device serves again
    assert svc.stats()["promotions"] == 1
    svc.stop()


def test_probe_rejects_wrong_verdict_device():
    """Re-promotion requires the canary to MATCH the stashed known-good
    verdict: a device that answers but answers wrong stays degraded."""
    class LyingBackend(StubBackend):
        def __init__(self):
            super().__init__()
            self.mode = "ok"

        def verify_batch(self, rounds, sigs, prev_sigs=None):
            if self.mode == "raise":
                raise ConnectionError("down")
            out = super().verify_batch(rounds, sigs, prev_sigs)
            return ~out if self.mode == "lie" else out

    svc = make_service(pad=8, probe_interval=5.0)
    dev, fb = LyingBackend(), StubBackend()
    h = svc.handle(SCHEME, PK, backend=dev, fallback=fb)
    assert h.verify_batch(*beacons([1, 2])).all()   # sample: round 1 -> True
    dev.mode = "raise"
    assert h.verify_batch(*beacons([3, 4])).all()   # degrade (via fallback)
    slot = svc._slots[h.key]
    assert slot.state == "degraded"
    dev.mode = "lie"                                # answers, wrongly
    svc.clock.advance(6.0)
    time.sleep(0.5)                                 # let the probe run
    assert slot.state in ("degraded", "probing")
    assert svc.stats()["promotions"] == 0
    svc.stop()


def test_partials_fall_back_to_host_factory_on_device_failure():
    """Live partial aggregation survives device loss: the opaque call is
    retried once, then the lane verifier falls back to the host factory
    instead of costing the round."""
    svc = make_service()
    calls = {"dev": 0, "host": 0}

    def dev_factory(scheme, poly, n):
        def verify(msg, ps):
            calls["dev"] += 1
            raise ConnectionError("device gone")
        return types.SimpleNamespace(verify=verify, kind="device")

    def host_factory(scheme, poly, n):
        def verify(msg, ps):
            calls["host"] += 1
            return [True] * len(ps)
        return types.SimpleNamespace(verify=verify, kind="host")

    pv = svc.partials_factory(dev_factory, fallback_factory=host_factory)(
        SCHEME, None, 3)
    assert pv.verify(b"m", [b"p1", b"p2"]) == [True, True]
    assert calls["dev"] == 2 and calls["host"] == 1
    svc.stop()


def test_service_threads_are_named_and_reaped():
    svc = make_service()
    h = svc.handle(SCHEME, PK, backend=StubBackend())
    assert h.verify_batch(*beacons([1])).all()
    sched = svc._streams[h.gid].thread
    wd = svc._watchdog_thread
    assert sched.name == f"verify-scheduler-g{h.gid}"
    assert wd.name == "verify-watchdog"
    svc.stop()
    sched.join(5)
    wd.join(5)
    assert not sched.is_alive() and not wd.is_alive()


# -- seeded device-fault chaos (ISSUE 7 acceptance) ---------------------------


@pytest.fixture(scope="module")
def chaos_chain():
    from chaos import TrueChain
    return TrueChain(n=24)


def test_device_flap_chaos_scenario(chaos_chain):
    """The acceptance scenario: mixed live/background workload through a
    flapping device — every future resolves, verdicts identical to a
    host-only run, failover within one watchdog deadline, re-promotion
    after recovery, then the device serves again."""
    from chaos import DeviceChaosScenario

    result = DeviceChaosScenario(seed=1234, rounds=24,
                                 chain=chaos_chain).run()
    assert result.all_resolved
    assert result.verdicts_match_host
    assert result.failovers >= 1
    assert result.failover_latency is not None
    assert result.failover_latency <= result.deadline
    assert result.repromoted and result.final_state == "healthy"
    assert result.device_served_after_recovery
    assert result.ok


def test_device_flap_scenario_is_seed_deterministic(chaos_chain):
    from chaos import DeviceChaosScenario

    r1 = DeviceChaosScenario(seed=77, chain=chaos_chain).run()
    r2 = DeviceChaosScenario(seed=77, chain=chaos_chain).run()
    assert r1.ok and r2.ok
    assert r1.failovers == r2.failovers
    assert r1.verdicts_match_host and r2.verdicts_match_host


def test_device_death_mid_catchup_sync_converges_via_host(chaos_chain):
    """Kill the device backend mid-catch-up-sync on a 3-node network:
    the sync plane must converge through the host failover path before
    the round deadline."""
    from chaos import DeviceFailoverSyncScenario

    result = DeviceFailoverSyncScenario(seed=99, rounds=24,
                                        chain=chaos_chain).run()
    assert result.converged
    assert result.degraded                  # the device really died mid-sync
    assert not result.faulty_after_sync
    assert result.elapsed <= result.period
    assert result.ok
