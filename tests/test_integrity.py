"""Chain-integrity subsystem (chain/integrity.py + SyncManager.heal +
tools/chain_doctor.py): seeded at-rest storage faults are detected,
quarantined, repaired from peers, and the post-repair full-crypto rescan
is clean — all with a fake clock and in-memory peers (zero network I/O).
"""

import os
import sys
import threading
import time

import pytest

from drand_tpu.chain import integrity
from drand_tpu.chain.beacon import Beacon, genesis_beacon
from drand_tpu.chain.errors import ErrMissingPrevious
from drand_tpu.chain.integrity import (INVALID_SIG, MALFORMED, MISSING,
                                       UNLINKED, IntegrityScanner)
from drand_tpu.chain.memdb import MemDBStore
from drand_tpu.chain.sqlitedb import SqliteStore
from drand_tpu.crypto.hostverify import HostBatchVerifier

from chaos import (BIT_FLIP, DELETED_ROW, TORN_WRITE, StorageChaosScenario,
                   StorageFaultPlan, TrueChain, inject_storage_faults,
                   stable_seed)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

N = 24

pytestmark = pytest.mark.storage


@pytest.fixture(scope="module")
def chain():
    return TrueChain(n=N)


def _seeded_store(chain, store=None, upto=N, genesis=False):
    store = store if store is not None else MemDBStore(buffer_size=100)
    if genesis:
        store.put(genesis_beacon(chain.genesis_seed))
    for r in range(1, upto + 1):
        store.put(chain.beacons[r])
    return store


def _scanner(chain, store, verifier=None, chunk=8):
    return IntegrityScanner(
        store, chain.scheme,
        verifier=verifier or HostBatchVerifier(chain.scheme, chain.public),
        genesis_seed=chain.genesis_seed, chunk=chunk,
        beacon_id="test-integrity")


# ---------------------------------------------------------------------------
# scanner unit tests
# ---------------------------------------------------------------------------


def test_scan_clean_chain_memdb_and_sqlite(chain, tmp_path):
    for store in (_seeded_store(chain),
                  _seeded_store(chain, SqliteStore(str(tmp_path / "c.db")),
                                genesis=True)):
        report = _scanner(chain, store).scan(mode="full")
        assert report.clean
        assert report.scanned == N
        assert report.upto == N
        store.close()


def test_scan_empty_store_is_clean(chain):
    report = _scanner(chain, MemDBStore(buffer_size=100)).scan(mode="full")
    assert report.clean and report.scanned == 0


def test_scan_empty_store_with_upto_flags_all_missing(chain):
    """A wiped store is NOT clean when the caller names a target: every
    round up to `upto` is a MISSING finding (full truncation must not
    scan healthy)."""
    report = _scanner(chain, MemDBStore(buffer_size=100)).scan(
        mode="full", upto=7)
    assert not report.clean
    assert report.rounds(MISSING) == list(range(1, 8))


def test_scan_flags_each_fault_kind(chain):
    store = _seeded_store(chain)
    # deterministic handcrafted faults at known rounds
    store.delete(5)                                     # hole
    b9 = store.get(9)
    store.delete(9)
    store.put(Beacon(round=9, signature=b9.signature[:40],
                     previous_sig=b9.previous_sig))     # torn write
    b14 = store.get(14)
    sig = bytearray(b14.signature)
    sig[7] ^= 0x10
    store.delete(14)
    store.put(Beacon(round=14, signature=bytes(sig),
                     previous_sig=b14.previous_sig))    # bit flip
    report = _scanner(chain, store).scan(mode="full")
    assert 5 in report.rounds(MISSING)
    assert 9 in report.rounds(MALFORMED)
    assert 14 in report.rounds(INVALID_SIG)
    # the round ABOVE a corrupt row failed verification only because its
    # anchor is corrupt — unprovable (UNLINKED), not provably invalid
    assert 15 not in report.rounds(INVALID_SIG)
    assert 15 in report.rounds(UNLINKED)
    # healthy rounds away from the damage are not flagged
    for r in (2, 3, 12, 20, N):
        assert r not in report.faulty_rounds
    # missing rounds have no row to quarantine; the others do
    assert 5 not in report.quarantinable_rounds
    assert {9, 14} <= set(report.quarantinable_rounds)


def test_scan_linkage_mode_needs_no_verifier(chain):
    store = _seeded_store(chain)
    store.delete(7)
    scanner = IntegrityScanner(store, chain.scheme,
                               genesis_seed=chain.genesis_seed)
    report = scanner.scan(mode="linkage")
    assert report.rounds(MISSING) == [7]
    assert report.verifier == "none"
    with pytest.raises(ValueError):
        scanner.scan(mode="full")      # full mode requires a verifier


def test_scan_unlinked_explicit_previous(chain):
    """A stored previous_sig that contradicts the previous row's stored
    signature is flagged UNLINKED even when the signature itself is
    genuine (full-beacon stores like memdb persist previous_sig and it
    can rot independently)."""
    store = _seeded_store(chain)
    b10 = store.get(10)
    store.delete(10)
    store.put(Beacon(round=10, signature=b10.signature,
                     previous_sig=b"\x13" * 96))
    report = _scanner(chain, store).scan(mode="full")
    assert 10 in report.rounds(UNLINKED)


def test_scan_upto_extends_past_head(chain):
    """A truncated chain (deleted tail) is only visible when the caller
    says how long the chain SHOULD be."""
    store = _seeded_store(chain, upto=N - 3)
    report = _scanner(chain, store).scan(mode="full", upto=N)
    assert report.rounds(MISSING) == [N - 2, N - 1, N]


def test_quarantine_deletes_only_bad_rows(chain):
    store = _seeded_store(chain)
    store.delete(5)
    b9 = store.get(9)
    store.delete(9)
    store.put(Beacon(round=9, signature=b9.signature[:40],
                     previous_sig=b9.previous_sig))
    scanner = _scanner(chain, store)
    report = scanner.scan(mode="full")
    deleted = scanner.quarantine(report)
    assert 9 in deleted and 5 not in deleted
    with pytest.raises(Exception):
        store.get(9)
    assert store.get(2).signature == chain.beacons[2].signature


def test_quarantine_plain_list_skips_absent_rounds(chain):
    """A plain round list (daemon check-chain path) may include rounds
    that were never on disk; they must not count as quarantined (engines
    no-op missing deletes)."""
    from drand_tpu.metrics import integrity_quarantined

    store = _seeded_store(chain)
    store.delete(6)                     # 6 is already gone
    scanner = IntegrityScanner(store, chain.scheme,
                               beacon_id="test-quarantine-plain")
    before = integrity_quarantined.labels(
        "test-quarantine-plain")._value.get()
    deleted = scanner.quarantine([3, 6])
    assert deleted == [3]
    assert integrity_quarantined.labels(
        "test-quarantine-plain")._value.get() == before + 1


# ---------------------------------------------------------------------------
# the read-ahead: a full scan reads chunk k+1 while chunk k verifies
# ---------------------------------------------------------------------------

READER = "integrity-read-ahead"


def _reader_threads():
    return [t for t in threading.enumerate()
            if t.name == READER and t.is_alive()]


class _WatchedStore:
    """The store as the scanner sees it, plus the highest round its
    cursors have returned, whether one ran out, and the threads that
    opened them."""

    def __init__(self, store):
        self._store = store
        self.cv = threading.Condition()
        self.seen = 0
        self.ended = False
        self.cursor_threads = []
        self.read_at = {}           # round -> perf_counter when returned

    def last(self):
        return self._store.last()

    def get(self, round_):
        return self._store.get(round_)

    def cursor(self):
        self.cursor_threads.append(threading.current_thread())
        return _WatchedCursor(self, self._store.cursor())


class _WatchedCursor:
    def __init__(self, store, cur):
        self._store = store
        self._cur = cur

    def _saw(self, b):
        with self._store.cv:
            if b is None:
                self._store.ended = True
            else:
                self._store.seen = max(self._store.seen, b.round)
                self._store.read_at.setdefault(b.round, time.perf_counter())
            self._store.cv.notify_all()
        return b

    def seek(self, round_):
        return self._saw(self._cur.seek(round_))

    def next(self):
        return self._saw(self._cur.next())


class _GatedVerifier:
    """`verify_batch(chunk k)` answers only once the store's cursor has
    passed chunk k+1's first row (or run out), so a scan gets past it
    only if the store is read ahead of the verify; `timeouts` counts the
    calls that gave up waiting.  With `hold`, it then gives the reader
    that long to run further, and `ahead` keeps the most rows the cursor
    was past chunk k's last one."""

    def __init__(self, inner, store, fail_at=None, hold=0.0, timeout=10.0):
        self.inner, self.store = inner, store
        self.kind = getattr(inner, "kind", "host")
        self.fail_at, self.hold, self.timeout = fail_at, hold, timeout
        self.timeouts = self.ahead = 0
        self.calls = []

    def verify_batch(self, rounds, sigs, prev_sigs=None):
        self.calls.append(list(rounds))
        st, last = self.store, max(rounds)
        with st.cv:
            if not st.cv.wait_for(lambda: st.ended or st.seen > last,
                                  timeout=self.timeout):
                self.timeouts += 1
            # a reader two chunks ahead would pass chunk k+1's last row
            st.cv.wait_for(lambda: st.ended or st.seen > last + 8,
                           timeout=self.hold)
            self.ahead = max(self.ahead, st.seen - last)
        if self.fail_at is not None and self.fail_at in rounds:
            raise RuntimeError("verifier fault")
        return self.inner.verify_batch(rounds, sigs, prev_sigs)


def _flip(store, r, sig=None, previous_sig=None):
    b = store.get(r)
    store.delete(r)
    store.put(Beacon(round=r, signature=b.signature if sig is None else sig,
                     previous_sig=b.previous_sig if previous_sig is None
                     else previous_sig))


def _corrupt_case(chain, tmp_path, case):
    """-> (store, scan kwargs) for one corrupt-store case, chunk 8."""
    if case == "hole_below_trimmed":
        # sqlite without require_previous keeps (round, signature) only:
        # the row above a hole has no anchor to verify against
        store = _seeded_store(chain, SqliteStore(str(tmp_path / "t.db")),
                              genesis=True)
        store.delete(6)
        return store, {}
    store = _seeded_store(chain)
    if case == "gap":
        store.delete(5)
        store.delete(13)
    elif case == "malformed":
        _flip(store, 12, sig=store.get(12).signature[:40])
    elif case == "unlinked":
        _flip(store, 10, previous_sig=b"\x13" * 96)
    elif case == "invalid_sig":
        sig = bytearray(store.get(14).signature)
        sig[7] ^= 0x10
        _flip(store, 14, sig=bytes(sig))
    elif case == "resume":
        ck = _scanner(chain, store).scan(mode="full", upto=16).checkpoint
        sig = bytearray(store.get(21).signature)
        sig[3] ^= 0x01
        _flip(store, 21, sig=bytes(sig))
        return store, {"resume": ck, "upto": N}
    elif case == "next_chunk_finding":
        # chunk 2's first row is torn: the reader meets it while chunk 1
        # verifies, and chunk 1's watermark must still commit
        _flip(store, 9, sig=store.get(9).signature[:40])
    return store, {"upto": N}


def _observed_scan(chain, store, verifier, tag, **kw):
    """A full scan's report fields, its progress calls and its
    chain_integrity_beacons_scanned delta."""
    from drand_tpu.metrics import integrity_beacons_scanned
    counter = integrity_beacons_scanned.labels(tag, verifier.kind, "startup")
    before = counter._value.get()
    calls = []
    report = IntegrityScanner(
        store, chain.scheme, verifier=verifier,
        genesis_seed=chain.genesis_seed, chunk=8, beacon_id=tag,
    ).scan(mode="full", progress=lambda d, u: calls.append((d, u)), **kw)
    return ((report.findings, report.scanned, report.checkpoint,
             report.resumed_from, report.upto, report.verifier),
            calls, counter._value.get() - before)


@pytest.mark.parametrize("case", ["gap", "malformed", "unlinked",
                                  "invalid_sig", "hole_below_trimmed",
                                  "resume", "next_chunk_finding"])
def test_read_ahead_reports_match_serial_scan(chain, tmp_path, monkeypatch,
                                              case):
    """A full scan that reads one chunk ahead reports exactly what the
    same walk gives run inline, one chunk after the other: findings,
    counts, watermark, progress calls and the scanned counter.  Every
    verify call waits until the cursor has passed the next chunk's first
    row, so the reader is always a chunk ahead when a chunk flushes."""
    store, kw = _corrupt_case(chain, tmp_path, case)
    host = HostBatchVerifier(chain.scheme, chain.public)
    watched = _WatchedStore(store)
    gated = _GatedVerifier(host, watched)
    ahead = _observed_scan(chain, watched, gated, f"ahead-{case}", **kw)
    assert gated.timeouts == 0          # the overlap engaged every chunk
    assert watched.cursor_threads[0].name == READER
    assert not _reader_threads()
    with monkeypatch.context() as m:
        m.setattr(integrity, "_ReadAhead", lambda records: records)
        serial = _observed_scan(chain, store, host, f"serial-{case}", **kw)
    assert ahead == serial
    (findings, _, checkpoint, resumed_from, _, _), calls, _ = ahead
    assert findings
    if case == "next_chunk_finding":
        assert checkpoint.round == 8
    if case == "resume":
        assert resumed_from == 16 and calls[0] == (24, N)
    store.close()


class _Cancelled(BaseException):
    """An early exit that is no Exception, as a cancelled caller's."""


@pytest.mark.parametrize("end", ["completes", "reader_error",
                                 "verifier_error", "progress_error",
                                 "linkage"])
def test_read_ahead_thread_ends_with_the_scan(chain, tmp_path, monkeypatch,
                                              end):
    """The reader runs at most one chunk ahead (8 rows here) and never
    outlives `scan()`: a reader-side error surfaces on the caller after
    the chunks read before it have been verified and reported; a
    verifier or progress error stops and joins the reader; a linkage
    scan reads inline and starts no thread."""
    built, read_ahead = [], integrity._ReadAhead

    def spy(records):
        built.append(records)
        return read_ahead(records)

    monkeypatch.setattr(integrity, "_ReadAhead", spy)
    store = _seeded_store(chain, SqliteStore(str(tmp_path / "s.db"),
                                             require_previous=True),
                          genesis=True)
    watched = _WatchedStore(store)
    verifier = _GatedVerifier(HostBatchVerifier(chain.scheme, chain.public),
                              watched, fail_at=12 if end == "verifier_error"
                              else None, hold=0.2)
    calls = []

    def progress(done, upto):
        calls.append(done)
        if end == "progress_error":
            raise _Cancelled()

    scanner = IntegrityScanner(watched, chain.scheme, verifier=verifier,
                               genesis_seed=chain.genesis_seed, chunk=8)
    if end == "completes":
        report = scanner.scan(mode="full", progress=progress)
        assert report.clean and calls == [8, 16, 24, N]
    elif end == "reader_error":
        store.delete(20)        # reading round 21 raises on the reader
        with pytest.raises(ErrMissingPrevious):
            scanner.scan(mode="full", progress=progress)
        assert calls == [8, 16]
        assert verifier.calls == [list(range(1, 9)), list(range(9, 17))]
    elif end == "verifier_error":
        with pytest.raises(RuntimeError, match="verifier fault"):
            scanner.scan(mode="full", progress=progress)
        assert calls == [8]
    elif end == "progress_error":
        with pytest.raises(_Cancelled):
            scanner.scan(mode="full", progress=progress)
        assert calls == [8]
    else:
        report = scanner.scan(mode="linkage", progress=progress)
        assert report.clean and calls == [8, 16, 24, N]
        assert watched.cursor_threads == [threading.current_thread()]
        assert built == []
    if end != "linkage":
        assert len(built) == 1 and verifier.timeouts == 0
        assert 0 < verifier.ahead <= 8
        assert watched.cursor_threads[0].name == READER
    assert not _reader_threads()
    store.close()


class _SlowVerifier:
    """Every signature verifies, after the next of `seconds` (the last
    one from then on) with the interpreter lock released, as a device
    pass; keeps each call's start and end."""

    kind = "device"

    def __init__(self, *seconds):
        self.seconds = list(seconds)
        self.calls = []

    def verify_batch(self, rounds, sigs, prev_sigs=None):
        t0 = time.perf_counter()
        time.sleep(self.seconds[min(len(self.calls), len(self.seconds) - 1)])
        self.calls.append((t0, time.perf_counter()))
        return [True] * len(rounds)


def test_read_ahead_centres_each_read_in_the_verify(chain):
    """From the third chunk on, the reader starts the next chunk's read
    about half-way into the caller's slack (its last record's time less
    the last read's): clear of the verifier's own host work at the start
    of a verify, and done before the verify ends."""
    watched = _WatchedStore(_seeded_store(chain))
    verifier = _SlowVerifier(0.2)
    report = IntegrityScanner(watched, chain.scheme, verifier=verifier,
                              genesis_seed=chain.genesis_seed,
                              chunk=4).scan(mode="full")
    assert report.clean and len(verifier.calls) == N // 4
    for k, (t0, t1) in enumerate(verifier.calls[1:-1], start=1):
        first_row = watched.read_at[4 * (k + 1) + 1]  # chunk k+1's
        assert t0 + 0.05 < first_row < t1, k


def test_read_ahead_wait_ends_when_the_caller_asks(chain):
    """A verify far shorter than the last one (the chunk after a
    bisection) does not make the caller sit out the reader's centring
    wait: asking for the next chunk ends it, so the caller waits no
    longer than a read."""
    from drand_tpu import metrics
    verifier = _SlowVerifier(0.4, 0.4, 0.4, 0.0)
    before = metrics.totals().get("integrity.wait", [0, 0.0])[1]
    report = IntegrityScanner(_seeded_store(chain), chain.scheme,
                              verifier=verifier,
                              genesis_seed=chain.genesis_seed,
                              chunk=4).scan(mode="full")
    assert report.clean and len(verifier.calls) == N // 4
    # without the early end, the read after the first fast verify waits
    # about (0.4 - read) / 2 = 0.2 s
    assert metrics.totals()["integrity.wait"][1] - before < 0.1


class _AllValid:
    """Every signature verifies: the stress test times the hand-off, not
    the pairing."""

    kind = "host"

    def verify_batch(self, rounds, sigs, prev_sigs=None):
        return [True] * len(rounds)


def test_read_ahead_scans_at_once_under_fast_thread_switching(chain,
                                                             monkeypatch):
    """More scans at once than cores, each with its reader, switching
    threads every 10 us: every report is the serial walk's, and no reader
    is left behind."""
    store = _seeded_store(chain)
    store.delete(5)
    _flip(store, 12, sig=store.get(12).signature[:40])

    def scan():
        return IntegrityScanner(store, chain.scheme, verifier=_AllValid(),
                                genesis_seed=chain.genesis_seed,
                                chunk=3).scan(mode="full", upto=N)

    with monkeypatch.context() as m:
        m.setattr(integrity, "_ReadAhead", lambda records: records)
        want = scan()
    got, errors = [], []

    def scans():
        try:
            for _ in range(10):
                got.append(scan())
        except Exception as e:      # reported below
            errors.append(e)

    threads = [threading.Thread(target=scans, name=f"stress-{i}")
               for i in range(2 * (os.cpu_count() or 4))]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert len(got) == 10 * len(threads)
    assert all(r == want for r in got)
    assert want.rounds(MISSING) == [5] and want.rounds(MALFORMED) == [12]
    assert not _reader_threads()


# ---------------------------------------------------------------------------
# the acceptance scenario: 3 nodes, seeded at-rest faults (torn write +
# bit flip + deleted row), zero network I/O
# ---------------------------------------------------------------------------


def test_storage_chaos_detect_quarantine_repair_converge(chain):
    scenario = StorageChaosScenario(seed=42, n_nodes=3, rounds=N,
                                    chain=chain)
    result = scenario.run()
    assert sorted(result.injected.values()) == sorted(
        [TORN_WRITE, BIT_FLIP, DELETED_ROW])
    assert result.all_detected, (result.injected, result.detected_rounds)
    assert result.unrepaired == []
    assert result.rescan_clean
    assert result.converged
    assert result.ok


def test_storage_chaos_deterministic_replay(chain):
    r1 = StorageChaosScenario(seed=7, rounds=N, chain=chain).run()
    r2 = StorageChaosScenario(seed=7, rounds=N, chain=chain).run()
    assert r1.injected == r2.injected
    assert r1.detected_rounds == r2.detected_rounds
    assert r1.chain_digest == r2.chain_digest
    # a different seed corrupts different rounds
    r3 = StorageChaosScenario(seed=8, rounds=N, chain=chain).run()
    assert r3.injected != r1.injected


def test_fault_plan_is_pure_function_of_seed():
    p = StorageFaultPlan(seed=stable_seed(3, "x"), torn_writes=2,
                         bit_flips=2, deleted_rows=1)
    assert p.assign(50) == p.assign(50)
    assert len(p.assign(50)) == 5


# ---------------------------------------------------------------------------
# sqlite end-to-end + the chain-doctor CLI (device verifier path)
# ---------------------------------------------------------------------------


def _doctor_db(chain, tmp_path, name="chain.db", faults=None):
    store = SqliteStore(str(tmp_path / name))
    _seeded_store(chain, store, genesis=True)
    if faults:
        inject_storage_faults(store, faults, N)
    store.close()
    return str(tmp_path / name)


def test_chain_doctor_scan_clean_uses_device_verifier(chain, tmp_path):
    """Acceptance: `chain_doctor.py scan` on an intact chain reports 0
    findings THROUGH the batched device verifier, proven by the
    chain_integrity_beacons_scanned{verifier="device"} counter."""
    from drand_tpu.metrics import integrity_beacons_scanned
    import chain_doctor

    db = _doctor_db(chain, tmp_path)
    counter = integrity_beacons_scanned.labels("default", "device",
                                               "startup")
    before = counter._value.get()
    # chunk 8 keeps the device pass on the pad-8 pipeline shape the batch
    # suite already compiles (cold XLA compiles are minutes on 2 CPU cores)
    sys_argv = ["chain_doctor.py", "scan", "--db", db,
                "--scheme", chain.scheme.id,
                "--pubkey", chain.public.hex(),
                "--genesis-seed", chain.genesis_seed.hex(),
                "--chunk", "8"]
    old = sys.argv
    sys.argv = sys_argv
    try:
        rc = chain_doctor.main()
    finally:
        sys.argv = old
    assert rc == 0
    assert counter._value.get() == before + N


def test_chain_doctor_repair_from_db(chain, tmp_path):
    """repair --from-db: corrupt chain + healthy backup -> clean rescan."""
    import chain_doctor

    bad = _doctor_db(chain, tmp_path, "bad.db",
                     faults=StorageFaultPlan(seed=stable_seed(5, "dr")))
    good = _doctor_db(chain, tmp_path, "good.db")
    old = sys.argv
    sys.argv = ["chain_doctor.py", "repair", "--db", bad,
                "--scheme", chain.scheme.id,
                "--pubkey", chain.public.hex(),
                "--genesis-seed", chain.genesis_seed.hex(),
                "--upto", str(N), "--host", "--from-db", good]
    try:
        rc = chain_doctor.main()
    finally:
        sys.argv = old
    assert rc == 0
    store = SqliteStore(bad)
    for r in range(1, N + 1):
        assert store.get(r).signature == chain.beacons[r].signature
    store.close()


def test_chain_doctor_repair_linkage_mode(chain, tmp_path):
    """repair --mode linkage: the initial scan is structural-only, but the
    post-repair rescan is still full-crypto (the scanner gains the repair
    verifier instead of crashing on the hard-coded full mode)."""
    import chain_doctor

    bad = _doctor_db(chain, tmp_path, "bad.db",
                     faults=StorageFaultPlan(seed=stable_seed(6, "lk"),
                                             bit_flips=0))
    good = _doctor_db(chain, tmp_path, "good.db")
    old = sys.argv
    sys.argv = ["chain_doctor.py", "repair", "--db", bad,
                "--scheme", chain.scheme.id,
                "--pubkey", chain.public.hex(),
                "--genesis-seed", chain.genesis_seed.hex(),
                "--upto", str(N), "--host", "--mode", "linkage",
                "--from-db", good]
    try:
        rc = chain_doctor.main()
    finally:
        sys.argv = old
    assert rc == 0


def test_startup_integrity_pass_glue(chain):
    """core/beacon_process._integrity_pass (startup trigger): scan synchronously,
    quarantine, repair on a background thread — exercised against a stub
    process so it needs no DKG, with in-memory peers and a fake clock."""
    import time
    from types import SimpleNamespace

    from drand_tpu.beacon.clock import FakeClock
    from drand_tpu.beacon.sync import SyncManager
    from drand_tpu.core.beacon_process import BeaconProcess
    from drand_tpu.core.follow import FollowFacade
    from drand_tpu.log import Logger

    victim = _seeded_store(chain)
    inject_storage_faults(
        victim, StorageFaultPlan(seed=stable_seed(9, "startup")), N)
    facade = FollowFacade(victim, chain.scheme.chained, chain.genesis_seed)

    def fetch(peer, from_round):
        for r in range(from_round, N + 1):
            yield chain.beacons[r]

    syncm = SyncManager(
        chain=facade, scheme=chain.scheme, public_key_bytes=chain.public,
        period=30, clock=FakeClock(1), fetch=fetch, peers=["peer0"],
        chunk=8, verifier=HostBatchVerifier(chain.scheme, chain.public))
    scanner = _scanner(chain, victim)

    class FakeChain:
        backend = victim

        def last(self):
            return victim.last()

        def integrity_scan(self, verifier=None, mode="full", upto=None,
                           progress=None, beacon_id="default", chunk=512,
                           trigger="startup", resume=None):
            return scanner.scan(mode=mode, upto=upto or N, resume=resume)

    import threading as _threading
    bp = SimpleNamespace(
        cfg=SimpleNamespace(startup_integrity="full"),
        syncm=syncm, handler=SimpleNamespace(chain=FakeChain()),
        _lock=_threading.Lock(), _repair_thread=None,
        log=Logger(), beacon_id="startup-test", _peers=lambda: ["peer0"],
        # clock-derived expected head (the head-truncation follow-up):
        # the real method needs group timing; the stub pins it to N
        _expected_head_round=lambda: N,
        _on_sync_needed=lambda target: None,
        # resumability plumbing (the stub keeps no watermark)
        _load_scan_checkpoint=lambda: None,
        _save_scan_checkpoint=lambda ck: None)
    BeaconProcess._integrity_pass(bp)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        if scanner.scan(mode="full", upto=N).clean:
            break
        time.sleep(0.05)
    assert scanner.scan(mode="full", upto=N).clean


def test_startup_scan_catches_head_truncation(chain):
    """ROADMAP follow-up: a deleted TAIL is invisible to a scan that asks
    the store its own length.  The startup pass derives the expected head
    from the clock (current_round), and a head behind it is flagged for
    CATCH-UP SYNC (one collapsing stream — ordinary downtime produces the
    same gap and must not be treated as corruption or fed to heal's
    per-round re-fetch) — instead of passing silently as clean."""
    from types import SimpleNamespace

    from drand_tpu.beacon.clock import FakeClock
    from drand_tpu.chain.timing import current_round, time_of_round
    from drand_tpu.core.beacon_process import BeaconProcess
    from drand_tpu.log import Logger

    period, genesis = 30, 1_000
    victim = _seeded_store(chain)
    for r in range(N - 2, N + 1):
        victim.delete(r)                 # the truncated tail

    # the store's own head says N-3: a store-head scan reports CLEAN
    assert _scanner(chain, victim).scan(mode="full").clean

    # the clock says we should be at round N
    now = time_of_round(period, genesis, N)
    bp = SimpleNamespace(clock=FakeClock(now),
                         group=SimpleNamespace(period=period,
                                               genesis_time=genesis))
    expected = BeaconProcess._expected_head_round(bp)
    assert expected == current_round(now, period, genesis) == N

    # the startup pass routes the missing suffix to catch-up sync
    scanner = _scanner(chain, victim)
    sync_requests = []

    class FakeChain:
        def last(self):
            return victim.last()

        def integrity_scan(self, verifier=None, mode="full", upto=None,
                           progress=None, beacon_id="default", chunk=512,
                           trigger="startup", resume=None):
            return scanner.scan(mode=mode, upto=upto, resume=resume)

    import threading as _threading
    bp_pass = SimpleNamespace(
        cfg=SimpleNamespace(startup_integrity="linkage"),
        syncm=SimpleNamespace(verifier=None),
        handler=SimpleNamespace(chain=FakeChain()),
        _lock=_threading.Lock(), _repair_thread=None,
        log=Logger(), beacon_id="truncation-test",
        _peers=lambda: [], clock=bp.clock, group=bp.group,
        _expected_head_round=lambda: expected,
        _on_sync_needed=sync_requests.append,
        _load_scan_checkpoint=lambda: None,
        _save_scan_checkpoint=lambda ck: None)
    BeaconProcess._integrity_pass(bp_pass)
    assert sync_requests == [expected]   # truncated tail -> catch-up sync

    # an up-to-date head (restart mid-round, head == expected - 1 — the
    # same grace /health applies) does NOT trip the probe
    for r in range(N - 2, N):
        victim.put(chain.beacons[r])     # restore through N-1
    sync_requests.clear()
    BeaconProcess._integrity_pass(bp_pass)
    assert sync_requests == []

    # before genesis nothing is expected (fresh network, empty store)
    bp_fresh = SimpleNamespace(clock=FakeClock(genesis - 1),
                               group=SimpleNamespace(period=period,
                                                     genesis_time=genesis))
    assert BeaconProcess._expected_head_round(bp_fresh) == 0


def test_heal_with_scan_report_quarantines_and_repairs(chain):
    """SyncManager.heal(ScanReport): quarantine metrics + repaired
    metrics + raw-store writeback."""
    from drand_tpu.beacon.clock import FakeClock
    from drand_tpu.beacon.sync import SyncManager
    from drand_tpu.core.follow import FollowFacade
    from drand_tpu.metrics import integrity_quarantined, integrity_repaired

    victim = _seeded_store(chain)
    inject_storage_faults(
        victim, StorageFaultPlan(seed=stable_seed(11, "heal")), N)
    facade = FollowFacade(victim, chain.scheme.chained, chain.genesis_seed)

    def fetch(peer, from_round):
        for r in range(from_round, N + 1):
            yield chain.beacons[r]

    syncm = SyncManager(
        chain=facade, scheme=chain.scheme, public_key_bytes=chain.public,
        period=30, clock=FakeClock(1), fetch=fetch, peers=["peer0"],
        chunk=8, verifier=HostBatchVerifier(chain.scheme, chain.public))
    scanner = _scanner(chain, victim)
    report = scanner.scan(mode="full", upto=N)
    assert not report.clean
    q_before = integrity_quarantined.labels("test-heal")._value.get()
    r_before = integrity_repaired.labels("test-heal")._value.get()
    remaining = syncm.heal(victim, report, beacon_id="test-heal")
    assert remaining == []
    assert integrity_quarantined.labels("test-heal")._value.get() > q_before
    assert integrity_repaired.labels("test-heal")._value.get() \
        == r_before + len(report.faulty_rounds)
    assert scanner.scan(mode="full", upto=N).clean


def test_heal_promotes_unprovable_successor_without_refetch(chain):
    """Two-phase quarantine (ROADMAP item 6): round 10 is bit-flipped, so
    round 11 — whose own bytes are intact — becomes UNPROVABLE (its
    anchor rotted).  heal must re-fetch ONLY round 10 from peers, then
    promote round 11 back from the quarantine side table once the anchor
    verifies, instead of re-downloading it."""
    from drand_tpu.beacon.clock import FakeClock
    from drand_tpu.beacon.sync import SyncManager
    from drand_tpu.core.follow import FollowFacade
    from drand_tpu.metrics import integrity_promoted

    victim = _seeded_store(chain)
    b10 = victim.get(10)
    victim.delete(10)
    sig = bytearray(b10.signature)
    sig[4] ^= 0x01
    victim.put(Beacon(round=10, signature=bytes(sig),
                      previous_sig=b10.previous_sig))

    scanner = _scanner(chain, victim)
    report = scanner.scan(mode="full", upto=N)
    assert 10 in report.rounds(INVALID_SIG)
    # round 11 is unprovable, not provably bad: every finding UNLINKED
    kinds_11 = {f.kind for f in report.findings if f.round == 11}
    assert kinds_11 == {UNLINKED}
    assert report.faulty_rounds == [10, 11]

    fetched = []

    def fetch(peer, from_round):
        fetched.append(from_round)
        for r in range(from_round, N + 1):
            yield chain.beacons[r]

    facade = FollowFacade(victim, chain.scheme.chained, chain.genesis_seed)
    syncm = SyncManager(
        chain=facade, scheme=chain.scheme, public_key_bytes=chain.public,
        period=30, clock=FakeClock(1), fetch=fetch, peers=["peer0"],
        chunk=8, verifier=HostBatchVerifier(chain.scheme, chain.public))
    p_before = integrity_promoted.labels("test-promote")._value.get()
    remaining = syncm.heal(victim, report, beacon_id="test-promote")
    assert remaining == []
    # only the provably-bad anchor hit the network
    assert 10 in fetched and 11 not in fetched
    assert integrity_promoted.labels("test-promote")._value.get() \
        == p_before + 1
    # promotion retired the tombstone and the chain re-verifies clean
    assert victim.tombstoned(11) is None
    assert victim.get(11).signature == chain.beacons[11].signature
    assert scanner.scan(mode="full", upto=N).clean


def test_heal_refetches_unprovable_when_promotion_fails(chain):
    """A tombstoned 'unprovable' row whose bytes are ACTUALLY bad (flipped
    after the anchor rotted) must fail promotion and fall through to the
    peer fetch — promotion never vouches for unverified bytes."""
    from drand_tpu.beacon.clock import FakeClock
    from drand_tpu.beacon.sync import SyncManager
    from drand_tpu.core.follow import FollowFacade

    victim = _seeded_store(chain)
    for r in (10, 11):      # flip BOTH: 11 reads unprovable but is forged
        b = victim.get(r)
        victim.delete(r)
        sig = bytearray(b.signature)
        sig[4] ^= 0x01
        victim.put(Beacon(round=r, signature=bytes(sig),
                          previous_sig=b.previous_sig))
    scanner = _scanner(chain, victim)
    report = scanner.scan(mode="full", upto=N)
    kinds_11 = {f.kind for f in report.findings if f.round == 11}
    assert kinds_11 == {UNLINKED}

    fetched = []

    def fetch(peer, from_round):
        fetched.append(from_round)
        for r in range(from_round, N + 1):
            yield chain.beacons[r]

    facade = FollowFacade(victim, chain.scheme.chained, chain.genesis_seed)
    syncm = SyncManager(
        chain=facade, scheme=chain.scheme, public_key_bytes=chain.public,
        period=30, clock=FakeClock(1), fetch=fetch, peers=["peer0"],
        chunk=8, verifier=HostBatchVerifier(chain.scheme, chain.public))
    remaining = syncm.heal(victim, report, beacon_id="test-promote-fail")
    assert remaining == []
    assert 11 in fetched        # promotion refused the forged bytes
    assert scanner.scan(mode="full", upto=N).clean


# ---------------------------------------------------------------------------
# scan resumability (ScanCheckpoint): scheduled scans resume at the clean
# prefix instead of rescanning from genesis
# ---------------------------------------------------------------------------


def test_scan_emits_and_honors_checkpoint(chain):
    """A clean scan yields a watermark; resuming from it scans only the
    delta, keeps the chained linkage anchor intact, and reports where it
    resumed."""
    store = _seeded_store(chain, upto=16)
    scanner = _scanner(chain, store)
    first = scanner.scan(mode="full", upto=16)
    assert first.clean and first.resumed_from == 0
    ck = first.checkpoint
    assert ck is not None and ck.round == 16 and ck.mode == "full"

    # idle chain (head == checkpoint): the resume must still be honored —
    # a zero delta is the cheapest scan of all, not a full-rescan trigger
    idle = scanner.scan(mode="full", upto=16, resume=ck)
    assert idle.clean and idle.resumed_from == 16 and idle.scanned == 0
    assert idle.checkpoint.round == 16

    for r in range(17, N + 1):          # the chain grows
        store.put(chain.beacons[r])
    second = scanner.scan(mode="full", upto=N, resume=ck)
    assert second.clean
    assert second.resumed_from == 16
    assert second.scanned == N - 16     # O(delta), not O(chain)
    assert second.checkpoint.round == N


def test_checkpoint_rejected_when_row_tampered(chain):
    """The watermark re-anchors against the stored row: a store rewritten
    beneath the checkpoint fails the signature-hash match and the scan
    falls back to a full walk (which then finds the tampering)."""
    store = _seeded_store(chain)
    scanner = _scanner(chain, store)
    ck = scanner.scan(mode="full", upto=16).checkpoint
    b = store.get(16)
    store.delete(16)
    store.put(Beacon(round=16, signature=b"\x00" * len(b.signature),
                     previous_sig=b.previous_sig))
    report = scanner.scan(mode="full", upto=N, resume=ck)
    assert report.resumed_from == 0     # full rescan, nothing vouched for
    assert report.scanned == N
    assert 16 in report.faulty_rounds


def test_checkpoint_freezes_at_first_finding(chain):
    """Corruption freezes the watermark at the last clean flush: the
    next resume re-examines the corrupt region instead of skipping it."""
    store = _seeded_store(chain)
    sig = store.get(18).signature
    store.delete(18)
    store.put(Beacon(round=18, signature=sig[: len(sig) // 2],
                     previous_sig=store.get(17).signature))
    scanner = _scanner(chain, store)    # chunk=8: flushes at 8, 16, 24
    report = scanner.scan(mode="full", upto=N)
    assert 18 in report.faulty_rounds
    assert report.checkpoint is not None
    assert report.checkpoint.round == 16   # last CLEAN flush boundary
    again = scanner.scan(mode="full", upto=N, resume=report.checkpoint)
    assert again.resumed_from == 16
    assert 18 in again.faulty_rounds    # the corruption is re-found


def test_linkage_checkpoint_not_honored_by_full_scan(chain):
    """A linkage-only watermark never proved any signature: a full-crypto
    scan must not skip its prefix (full checkpoints cover both modes)."""
    store = _seeded_store(chain)
    scanner = _scanner(chain, store)
    ck_link = scanner.scan(mode="linkage", upto=16).checkpoint
    assert ck_link.mode == "linkage"
    full = scanner.scan(mode="full", upto=N, resume=ck_link)
    assert full.resumed_from == 0 and full.scanned == N
    link = scanner.scan(mode="linkage", upto=N, resume=ck_link)
    assert link.resumed_from == 16      # linkage may resume from linkage


def test_scheduled_scan_resumes_and_reports_metric(chain):
    """BeaconProcess glue: trigger=scheduled loads the persisted
    watermark, passes it to the scan, records the new one, and sets the
    chain_integrity_scan_resumed_from gauge."""
    from types import SimpleNamespace

    from drand_tpu.core.beacon_process import BeaconProcess
    from drand_tpu.log import Logger
    from drand_tpu.metrics import integrity_scan_resumed_from

    store = _seeded_store(chain)
    scanner = _scanner(chain, store)
    prior = scanner.scan(mode="full", upto=16).checkpoint
    saved = {}
    scans = {}

    class FakeChain:
        def last(self):
            return store.last()

        def integrity_scan(self, verifier=None, mode="full", upto=None,
                           progress=None, beacon_id="default", chunk=512,
                           trigger="startup", resume=None):
            scans["resume"] = resume
            return scanner.scan(mode=mode, upto=upto or N, resume=resume)

    import threading as _threading
    bp = SimpleNamespace(
        cfg=SimpleNamespace(startup_integrity="full"),
        syncm=SimpleNamespace(verifier=None),
        handler=SimpleNamespace(chain=FakeChain()),
        _lock=_threading.Lock(), _repair_thread=None,
        log=Logger(), beacon_id="resume-test",
        _peers=lambda: [],
        _expected_head_round=lambda: 0,
        _on_sync_needed=lambda target: None,
        _load_scan_checkpoint=lambda: prior,
        _save_scan_checkpoint=lambda ck: saved.update(ck=ck))
    BeaconProcess._integrity_pass(bp, trigger="scheduled")
    assert scans["resume"] is prior
    assert saved["ck"].round == N       # watermark advanced
    gauge = integrity_scan_resumed_from.labels("resume-test")
    assert gauge._value.get() == 16
