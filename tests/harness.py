"""In-process multi-node test harness (the core/util_test.go:43-78 pattern):
n handlers share one FakeClock and exchange partials through a LocalNetwork
that can drop nodes (DenyClient-style fault injection).  Shares are
fabricated from a single polynomial (test/test.go BatchIdentities pattern) —
DKG-produced shares are exercised by the dkg tests instead."""

import threading
import time

from drand_tpu import metrics
from drand_tpu.beacon import FakeClock, Handler, HandlerConfig
from drand_tpu.chain import MemDBStore
from drand_tpu.crypto import tbls
from drand_tpu.crypto.schemes import scheme_from_name
from drand_tpu.key import DistPublic, Share, new_group, new_keypair


# every thread the verify service owns carries one of these names
# (crypto/verify_service.py); a daemon stop() must reap them all.
# "transition-" is the reshare transition waiter (core/beacon_process.py
# _start_at_transition): it parks on the process stop event, so a daemon
# stop must reap it too — it used to wait on a never-set Event and
# outlive the daemon (the leaked transition-<id> thread bug).
SERVICE_THREAD_PREFIXES = ("verify-scheduler", "verify-packer",
                           "verify-watchdog", "verify-probe",
                           "transition-", "handel-")

# the REST edge's threads (http_server.py): ONE acceptor + a FIXED worker
# pool — request traffic must never grow this set (the unbounded
# ThreadingHTTPServer thread-per-request bug this replaces)
REST_THREAD_PREFIXES = ("rest-edge", "rest-worker", "http-relay")


class OwnWork:
    """The registry events of a test's own work.  The registry is
    process-wide, and threads that earlier tests left behind in the same
    process (a test worker runs many files) may add to it meanwhile.  From
    construction on, every `metrics.add` (a span's exit too) is recorded
    with whether it came from the constructing thread or a thread started
    after construction (the test's service threads): `delta(since)` sums
    those alone, `delta(since, own=False)` every thread's."""

    def __init__(self, monkeypatch):
        here = threading.current_thread()
        theirs = set(threading.enumerate()) - {here}
        self.events = []
        add = metrics.add

        def recording(name, seconds=0.0, count=1):
            add(name, seconds, count)
            own = threading.current_thread() not in theirs
            self.events.append((own, name, count, seconds))

        monkeypatch.setattr(metrics, "add", recording)

    def mark(self) -> int:
        return len(self.events)

    def delta(self, since=0, own=True):
        out = {}
        for mine, name, n, secs in self.events[since:]:
            if mine or not own:
                c = out.setdefault(name, [0, 0.0])
                c[0] += n
                c[1] += secs
        return out


def service_threads():
    """Alive verify-service threads, for before/after leak accounting."""
    return [t for t in threading.enumerate()
            if t.is_alive()
            and any(t.name.startswith(p) for p in SERVICE_THREAD_PREFIXES)]


def rest_threads():
    """Alive REST-edge threads (acceptor + bounded worker pool)."""
    return [t for t in threading.enumerate()
            if t.is_alive()
            and any(t.name.startswith(p) for p in REST_THREAD_PREFIXES)]


def assert_no_leaked_rest_threads(before=(), timeout: float = 5.0):
    """Fail if any REST-edge thread outlives its server's stop().  Same
    snapshot-before contract as `assert_no_leaked_service_threads`."""
    exempt = set(id(t) for t in before)
    deadline = time.monotonic() + timeout
    leaked = [t for t in rest_threads() if id(t) not in exempt]
    while leaked and time.monotonic() < deadline:
        time.sleep(0.05)
        leaked = [t for t in rest_threads() if id(t) not in exempt]
    assert not leaked, (
        "leaked REST-edge threads after server stop: "
        + ", ".join(t.name for t in leaked))


def assert_no_leaked_service_threads(before=(), timeout: float = 5.0):
    """Fail if any verify-service thread outlives its daemon.  `before`
    (a `service_threads()` snapshot taken at setup) exempts threads that
    pre-date the code under test — e.g. the process-default singleton
    another test module's client spun up and never stops.  Threads get
    `timeout` real seconds to finish their bounded shutdown joins."""
    exempt = set(id(t) for t in before)
    deadline = time.monotonic() + timeout
    leaked = [t for t in service_threads() if id(t) not in exempt]
    while leaked and time.monotonic() < deadline:
        time.sleep(0.05)
        leaked = [t for t in service_threads() if id(t) not in exempt]
    assert not leaked, (
        "leaked verify-service threads after daemon stop: "
        + ", ".join(t.name for t in leaked))


class LocalNetwork:
    """Synchronous in-process partial delivery with per-node kill switches."""

    def __init__(self):
        self.handlers = {}
        self.down = set()
        self._lock = threading.Lock()

    def register(self, index, handler):
        with self._lock:
            self.handlers[index] = handler
            self.down.discard(index)

    def kill(self, index):
        with self._lock:
            self.down.add(index)

    def revive(self, index):
        with self._lock:
            self.down.discard(index)

    def broadcaster(self, sender_index):
        def broadcast(packet):
            with self._lock:
                targets = [(i, h) for i, h in self.handlers.items()
                           if i != sender_index and i not in self.down
                           and sender_index not in self.down]
            for _, h in targets:
                try:
                    h.process_partial_beacon(packet)
                except ValueError:
                    pass
        return broadcast


class BeaconScenario:
    """n-node beacon network under a stepped clock."""

    def __init__(self, n, thr, scheme_id="pedersen-bls-chained",
                 period=30, catchup_period=5, genesis_offset=100,
                 store_factory=None, secret=111222333):
        self.scheme = scheme_from_name(scheme_id)
        self.clock = FakeClock(start=1_000_000)
        self.net = LocalNetwork()
        self.period = period
        self.genesis = int(self.clock.now()) + genesis_offset

        pairs = [new_keypair(f"127.0.0.1:{9000 + i}", self.scheme,
                             seed=b"scenario%d" % i) for i in range(n)]
        self.group = new_group([p.public for p in pairs], thr,
                               genesis=self.genesis, period=period,
                               catchup_period=catchup_period,
                               scheme=self.scheme)
        self.poly = tbls.PriPoly.random(thr, secret=secret)
        commits = [self.scheme.key_group.to_bytes(c)
                   for c in self.poly.commit(self.scheme.key_group).commits]
        self.group.public_key = DistPublic(commits)
        self.commits = commits
        self.public_key = commits[0]
        self.store_factory = store_factory or (lambda i: MemDBStore(buffer_size=100))
        self.handlers = {}
        for node in self.group.nodes:
            self._make_handler(node.index)

    def _make_handler(self, index, store=None):
        share = Share(scheme=self.scheme, private=self.poly.eval(index),
                      commits=self.commits)
        h = Handler(HandlerConfig(
            group=self.group, share=share, index=index,
            store=store if store is not None else self.store_factory(index),
            clock=self.clock,
            broadcast=self.net.broadcaster(index)))
        self.net.register(index, h)
        self.handlers[index] = h
        return h

    def start_all(self):
        for h in self.handlers.values():
            h.start()

    def advance_to_genesis(self):
        self.clock.set_time(self.genesis)

    def advance_round(self):
        self.clock.advance(self.period)

    def wait_round(self, index, round_, timeout=60):
        b = self.handlers[index].chain.wait_for_round(
            round_, timeout, scheduled_time=True)
        assert b is not None, \
            f"node {index} never reached round {round_}"
        return b

    def wait_all(self, round_, timeout=60):
        """Wait until EVERY live node stored `round_` — advance the fake
        clock only after this, or lagging nodes consume the next tick while
        still aggregating (core/util_test.go waits all nodes the same way)."""
        return [self.wait_round(i, round_, timeout)
                for i in sorted(self.handlers)]

    def kill(self, index):
        self.net.kill(index)
        h = self.handlers.pop(index)
        store = h.cfg.store
        h.stop()
        return store

    def restart(self, index, store):
        h = self._make_handler(index, store=store)
        self.net.revive(index)
        h.catchup()
        return h

    def stop_all(self):
        for h in list(self.handlers.values()):
            h.stop()
