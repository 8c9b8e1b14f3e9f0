"""The `aggregate` traffic loop: one member node of a threshold group,
catching up round by round through the daemon's own `Handler`.

The node is the program's: a `Handler` over a `SqliteStore`, its
aggregation verifier built by the factory the daemon itself calls
(`core.beacon_process.aggregation_verifier_factory`: the device partial
verifier on the verify service's live lane, the host one behind it).  The
chain sits far behind the wall clock, so the Handler's fast-forward signs
and broadcasts round r+1 the moment round r is stored: a closed loop.  The
benchmark plays the rest of the group at the broadcast boundary: when the
node broadcasts its partial for round r, the online peers' partials for r
come back through `Handler.process_partial_beacon`, in an order drawn from
the seed.  It also records, at the verifier's boundary, every verdict the
aggregator got, and at the store's callback boundary every stored beacon.

The window opens on the first round after the warm-up and closes at the
first stored beacon past --seconds; each stored beacon is one entry of
`window.chunks`.  The whole window is one `scan.outside` host span: the
trace's idle gaps are not split by what the node was doing.

Mix parameters (`benchmark/traffic/<mix>.json`): `warm_rounds`, rounds
aggregated in set-up; `offline_peers`, peers (drawn from the seed) that
send nothing all run; `invalid_every`, where one round of each aligned
block of that many, at an offset drawn from the seed, has one online
peer (drawn from the seed) send its share's signature of the previous
round's message as the t-th arrival (the node's own partial is the
first), so that it completes the threshold and the first check fails.
Configuration keys: `group_size`, `threshold`, `node_index`,
`first_round` (the round after the stored checkpoint) and `rounds` (how
many the fixture signs ahead).

Compared numbers, each with the limit 0:
  unanswered                rounds the node got partials for in the
                            window and stored no beacon of, plus one for
                            a warm-up round that stalled (a round that
                            stalls STALL_S ends the run)
  beacon_mismatch           stored rounds whose signature, or whose
                            previous signature, differs from the
                            reference's group signature (`reftbls`)
  partial_verdict_mismatch  verdicts the aggregator got that differ from
                            the reference's exact check of that partial
                            over the round's message
  partials_fell_back        live partial calls that fell back to the host
                            (the program's `partials.fallback` counter)
"""

import gc
import multiprocessing
import os
import random
import threading
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor

from .. import reftbls

STALL_S = 10.0          # a window round stalled this long ends the run
WARM_STALL_S = 1800.0   # the first verdict: the program's first call


def _threads() -> int:
    return max(1, min(16, (os.cpu_count() or 3) - 2))


class GroupFixture:
    """The group, the chain's checkpoint and every round's arrivals, drawn
    from the seed; the signatures come from the reference."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.n, self.t = int(config["group_size"]), int(config["threshold"])
        self.me = int(config["node_index"])
        self.dealer = reftbls.Dealer(config["scheme"], self.n, self.t, seed)
        self.first = int(config["first_round"])
        self.checkpoint = self.first - 1
        self.last = self.checkpoint + int(config["rounds"])
        rng = random.Random(seed * 1_000_003 + 11)
        self.prev0 = rng.randbytes(96 if self.dealer.sig_group == "G2"
                                   else 48)
        peers = [i for i in range(self.n) if i != self.me]
        offline = set(rng.sample(peers, int(traffic["offline_peers"])))
        self.online = [i for i in peers if i not in offline]
        if len(self.online) + 1 < self.t + 1:
            raise ValueError("too few online peers for a late partial")
        # each round: the online peers' order; in one round of each
        # aligned block of `invalid_every`, at an offset drawn from the
        # seed, a planted partial's signer
        self.order, self.planted = {}, {}
        every = int(traffic["invalid_every"])
        for lo in range(self.first, self.last + 1, every):
            r = lo + rng.randrange(every)
            signer = rng.choice(self.online)
            if r <= self.last:
                self.planted[r] = signer
        for r in range(self.first, self.last + 1):
            self.order[r] = rng.sample(self.online, len(self.online))
        self.beacons = {self.checkpoint - 1: self.prev0}
        self.beacons[self.checkpoint] = self.dealer.beacon(
            self.message(self.checkpoint))
        self.arrivals = {}      # round -> [wire partial], delivery order

    def message(self, round_: int) -> bytes:
        return self.dealer.message(round_, self.beacons[round_ - 1])

    def sign(self, lo: int, hi: int) -> None:
        """Sign rounds lo..hi here: the group's beacons in order, then every
        round's arrivals on host threads."""
        for r in range(lo, hi + 1):
            self.beacons[r] = self.dealer.beacon(self.message(r))
        with ThreadPoolExecutor(max_workers=_threads()) as ex:
            for r, arr in zip(range(lo, hi + 1),
                              ex.map(self._arrivals, range(lo, hi + 1))):
                self.arrivals[r] = arr

    def _arrivals(self, r: int) -> list:
        msg = self.message(r)
        plant = self.planted.get(r)
        order = list(self.order[r])
        if plant is None:
            return [self.dealer.partial(i, msg) for i in order]
        order.remove(plant)
        out = [self.dealer.partial(i, msg) for i in order]
        # the t-th arrival, the node's own partial being the first
        out.insert(self.t - 2, self.dealer.partial(plant, self.message(r - 1)))
        return out

    def sign_elsewhere(self, lo: int, hi: int):
        """Sign rounds lo..hi in a child process (it imports no JAX), so
        that this one can trace and compile meanwhile; `.result()` waits
        and takes them in."""
        return _Elsewhere(self, lo, hi)


def _sign_rounds(config, traffic, seed, lo, hi, prevs):
    fx = GroupFixture(config, traffic, seed)
    fx.beacons[lo - 2], fx.beacons[lo - 1] = prevs
    fx.sign(lo, hi)
    return ({r: fx.beacons[r] for r in range(lo, hi + 1)},
            {r: fx.arrivals[r] for r in range(lo, hi + 1)})


class _Elsewhere:
    def __init__(self, fx: GroupFixture, lo: int, hi: int):
        self.fx = fx
        self.pool = ProcessPoolExecutor(
            max_workers=1, mp_context=multiprocessing.get_context("spawn"))
        self.future = self.pool.submit(
            _sign_rounds, fx.config, fx.traffic, fx.seed, lo, hi,
            (fx.beacons[lo - 2], fx.beacons[lo - 1]))

    def result(self) -> None:
        beacons, arrivals = self.future.result()
        self.fx.beacons.update(beacons)
        self.fx.arrivals.update(arrivals)

    def close(self) -> None:
        self.future.cancel()
        self.pool.shutdown(wait=True)


class FullCollections:
    """Counts the interpreter's full (generation 2) collections, and their
    seconds, while it is in `gc.callbacks`."""

    def __init__(self):
        self.n, self.seconds, self._t = 0, 0.0, None

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.n += 1
            self.seconds += time.perf_counter() - self._t
            self._t = None


class VerdictLog:
    """The aggregator's partial verifier (the factory's `verify`, or what
    a test put in its place), with every call's message, partials and
    verdicts kept for the check."""

    def __init__(self, verify):
        self._verify = verify
        self.calls = []

    def verify(self, msg: bytes, partials):
        ok = self._verify(msg, partials)
        self.calls.append((bytes(msg), [bytes(p) for p in partials],
                           [bool(v) for v in ok]))
        return ok


class Run:
    """One run of the loop over `env` (see `cell.Env`)."""

    def __init__(self, env):
        self.env = env
        self.fx = self.handler = self.rest = self.log = None
        self.db = os.path.join(env.tmp, "chain.db")
        self._cond = threading.Condition()
        self._go = threading.Event()
        self.stored = {}            # round -> (signature, previous_sig)
        self.through = 0            # highest round the callback recorded
        self.delivered = set()
        self.error = None
        self.warm_hi = 0
        self.warm_stalled = False   # a warm-up round stalled: no window

    # -- the group around the node --------------------------------------

    def _broadcast(self, packet) -> None:
        """The node's broadcast: the online peers answer with their
        partials for the same round, once a round."""
        from drand_tpu.beacon.node import PartialBeaconPacket
        r, w = packet.round, self.env.window
        with self._cond:
            if not self._cond.wait_for(lambda: self.through >= r - 1
                                       or self.error, timeout=STALL_S):
                return
            if r in self.delivered or self.error or w.closed:
                return
            if r > self.fx.last:
                self.error = (f"the run passed the fixture's last round "
                              f"{self.fx.last}: raise `rounds`")
                self._cond.notify_all()
                return
            self.delivered.add(r)
        if r > self.warm_hi:
            self._go.wait()
            if w.closed:
                return
            w.submitted += 1
        for p in self.fx.arrivals[r]:
            self.handler.process_partial_beacon(PartialBeaconPacket(
                round=r, previous_signature=packet.previous_signature,
                partial_sig=p))

    def _on_stored(self, beacon) -> None:
        t = time.perf_counter()
        w = self.env.window
        with self._cond:
            self.stored[beacon.round] = (bytes(beacon.signature),
                                         beacon.previous_sig)
            self.through = max(self.through, beacon.round)
            if w.recording:
                w.chunks.append((0, [beacon.round], [beacon.signature],
                                 [beacon.previous_sig], [True]))
                if t - w.start >= w.seconds:
                    w.end = t
                    w.closed = True
            self._cond.notify_all()

    def _wait_through(self, round_: int, stall: float,
                      first_call: bool = False) -> bool:
        """Wait until `round_` is stored or the window closes; -> False if
        nothing moved (no beacon stored, no verdict returned) for `stall`
        seconds first.  `first_call`: the partials program's first call is
        due, so the first verdict may take WARM_STALL_S."""
        with self._cond:
            seen, t_seen = self._progress(), time.monotonic()
            while self.through < round_ and not self.error \
                    and not self.env.window.closed:
                self._cond.wait(0.5)
                now = self._progress()
                if now != seen:
                    seen, t_seen = now, time.monotonic()
                    continue
                limit = WARM_STALL_S if first_call and not now[1] else stall
                if time.monotonic() - t_seen > limit:
                    return False
            if self.error:
                raise RuntimeError(self.error)
            return True

    def _progress(self):
        return self.through, len(self.log.calls) if self.log else 0

    # -- set-up, window, check ----------------------------------------

    def setup(self) -> str:
        """Fixture, store, node and warm-up; -> a line for the log."""
        try:
            from drand_tpu.core.beacon_process import \
                aggregation_verifier_factory
        except ImportError as e:
            raise RuntimeError(
                "this program has no aggregation_verifier_factory (the "
                "daemon's fixed-shape partials path), which this cell "
                "runs") from e
        from drand_tpu import metrics
        from drand_tpu.beacon.node import Handler, HandlerConfig
        from drand_tpu.chain.beacon import Beacon
        from drand_tpu.chain.sqlitedb import SqliteStore
        from drand_tpu.crypto import schemes, tbls
        from drand_tpu.key import DistPublic, Share, new_group, new_keypair

        env, cfg = self.env, self.env.config
        self.fallback0 = metrics.totals().get("partials.fallback", [0])[0]
        t0 = time.monotonic()
        fx = self.fx = GroupFixture(cfg, env.traffic, env.seed)
        self.through = fx.checkpoint
        self.warm_hi = fx.checkpoint + int(env.traffic["warm_rounds"])
        fx.sign(fx.first, self.warm_hi)
        self.rest = fx.sign_elsewhere(self.warm_hi + 1, fx.last)
        t_sign = time.monotonic() - t0

        scheme = schemes.scheme_from_name(cfg["scheme"])
        store = SqliteStore(self.db, require_previous=scheme.chained)
        store.put_many([Beacon(round=r, signature=fx.beacons[r])
                        for r in (fx.checkpoint - 1, fx.checkpoint)])
        pairs = [new_keypair(f"node{i}.bench:443", scheme,
                             seed=b"bench-node%d-%d" % (i, env.seed))
                 for i in range(fx.n)]
        group = new_group([p.public for p in pairs], fx.t,
                          genesis=int(cfg["genesis_time"]),
                          period=int(cfg["period"]), catchup_period=0,
                          scheme=scheme)
        group.public_key = DistPublic(list(fx.dealer.commits))
        share = Share(scheme=scheme, private=tbls.PriShare(
            fx.me, fx.dealer.shares[fx.me]), commits=list(fx.dealer.commits))
        factory = aggregation_verifier_factory(
            env.service, env.daemon.use_device_verifier)

        def logged(scheme_, pub_poly, n):
            verify = factory(scheme_, pub_poly, n).verify
            if env._wrap is not None:
                verify = env._wrap(verify, fx)
            self.log = VerdictLog(verify)
            return self.log

        self.handler = Handler(HandlerConfig(
            group=group, share=share, index=fx.me, store=store,
            verifier_factory=logged, broadcast=self._broadcast))
        self.handler.chain.cbstore.add_callback("bench-window",
                                                self._on_stored)
        t_warm = time.monotonic()
        self.handler.start()
        self.handler.broadcast_next_partial(self.handler.chain.last())
        if not self._wait_through(self.warm_hi, STALL_S, first_call=True):
            self.warm_stalled = True
        t_warm = time.monotonic() - t_warm
        t_rest = time.monotonic()
        self.rest.result()
        # Tracing the partials program leaves about 1.5 M objects in JAX's
        # caches, and a full collection of them takes over a second.  One
        # runs once the objects kept since the last reach a quarter of
        # those: whichever set-up has brought near runs here, not in the
        # window.
        gc.collect()
        return (f"{fx.n} nodes, threshold {fx.t}, {len(fx.online)} online "
                f"peers, {len(fx.planted)} planted rounds in "
                f"{fx.last - fx.checkpoint}; warm-up rounds signed in "
                f"{t_sign:.1f} s, {self.warm_hi - fx.checkpoint} aggregated "
                f"in {t_warm:.1f} s ({self._planted(fx.first, self.warm_hi)} "
                f"planted{'; a round stalled' if self.warm_stalled else ''})"
                f", then waited {time.monotonic() - t_rest:.1f} s for the "
                f"rest of the fixture, signed meanwhile")

    def _planted(self, lo: int, hi: int) -> int:
        return sum(1 for r in self.fx.planted if lo <= r <= hi)

    def measure(self) -> str:
        w = self.env.window
        full = FullCollections()
        gc.callbacks.append(full)
        w.open()
        self._go.set()
        try:
            stalled = self.warm_stalled \
                or not self._wait_through(self.fx.last, STALL_S)
            with self._cond:
                if not w.closed:
                    w.end = time.perf_counter()
                    w.closed = True
                self._cond.notify_all()
        finally:
            gc.callbacks.remove(full)
        w._leave_outside(w.end)
        self.handler.stop()
        self.handler = None
        last = max(self.stored, default=self.fx.checkpoint)
        planted = self._planted(self.warm_hi + 1, last)
        return (f"rounds up to {last}, {planted} planted, {full.n} full "
                f"collections ({full.seconds:.2f} s)"
                f"{'; a round stalled' if stalled else ''}")

    def close(self) -> None:
        self._go.set()
        if self.handler is not None:
            self.handler.stop()
        if self.rest is not None:
            self.rest.close()

    def check(self):
        """-> (checks {name: (value, limit)}, rounds compared)."""
        from drand_tpu import metrics
        from drand_tpu.chain.sqlitedb import SqliteStore
        fx, w = self.fx, self.env.window
        rows = {}
        store = SqliteStore(self.db, require_previous=True)
        try:
            for r in sorted(self.stored):
                b = store.get(r)
                rows[r] = (bytes(b.signature), b.previous_sig)
        finally:
            store.close()
        beacon_mismatch = 0
        for r, (sig, prev) in self.stored.items():
            want = (fx.beacons.get(r), fx.beacons.get(r - 1))
            beacon_mismatch += (sig, prev) != want or rows[r] != want
        round_of = {fx.message(r): r for r in fx.beacons
                    if r > fx.checkpoint - 1 and r - 1 in fx.beacons}
        items = [(msg, p, v) for msg, ps, vs in self.log.calls
                 for p, v in zip(ps, vs)]
        with ThreadPoolExecutor(max_workers=_threads()) as ex:
            ref = list(ex.map(
                lambda it: it[0] in round_of
                and fx.dealer.verify_partial(it[0], it[1]), items))
        verdict_mismatch = sum(v != want for (_, _, v), want
                               in zip(items, ref))
        fell = metrics.totals().get("partials.fallback", [0])[0] \
            - self.fallback0
        checks = {"unanswered": (w.submitted - w.rounds
                                 + self.warm_stalled, 0),
                  "beacon_mismatch": (beacon_mismatch, 0),
                  "partial_verdict_mismatch": (verdict_mismatch, 0),
                  "partials_fell_back": (fell, 0)}
        return checks, len(self.stored)
