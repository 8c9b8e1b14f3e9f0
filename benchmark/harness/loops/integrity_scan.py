"""The `integrity_scan` traffic loop: one closed-loop caller, the daemon's
full-mode integrity scan, run back to back over the cell's store.

The scanner is the program's own `IntegrityScanner`; the benchmark only
wraps its two boundaries: the verifier (`window.VerifyProxy`) and the
store (`StoreView`, which ends the store's cursor once the window has
closed, so the scan stops at that chunk boundary and returns its report
as if the store ended there).

Mix parameters (`benchmark/traffic/<mix>.json`): `warm_rounds`, the
rounds the warm-up scan verifies, and `corrupt_block`, where each aligned
block of that many rounds holds one planted row (0: none).
"""

import hashlib
import os
import time

from .. import check
from ..fixture import ChainFixture


class StoreView:
    """The store as the scanner sees it: reads pass through, and the
    cursor ends once the window has closed."""

    def __init__(self, store, window):
        self._store = store
        self._window = window

    def last(self):
        return self._store.last()

    def get(self, round_):
        return self._store.get(round_)

    def cursor(self):
        return _CursorView(self._store.cursor(), self._window)


class _CursorView:
    def __init__(self, cur, window):
        self._cur = cur
        self._window = window

    def seek(self, round_):
        return None if self._window.closed else self._cur.seek(round_)

    def next(self):
        return None if self._window.closed else self._cur.next()


def scan_until_closed(make_scanner, resume, window) -> list:
    """Run full scans back to back until the window closes.  Returns one
    (report, last round whose verdict came in the window) per scan."""
    from drand_tpu.chain.integrity import MODE_FULL
    out = []
    window.open()
    while not window.closed:
        n_before = len(window.chunks)
        report = make_scanner().scan(mode=MODE_FULL, resume=resume)
        mine = window.chunks[n_before:]
        cut = max((max(c[1]) for c in mine), default=0)
        out.append((report, cut))
        window.scan_index += 1
    return out


class Run:
    """One run of the loop over `env` (see `cell.Env`)."""

    def __init__(self, env):
        self.env = env
        self.fx = self.store = self.tail = None
        self.reports = []

    def setup(self) -> str:
        """Store, verifier and warm-up scan; -> a line for the log."""
        from drand_tpu.chain.beacon import Beacon
        from drand_tpu.chain.integrity import (IntegrityScanner, MODE_FULL,
                                               ScanCheckpoint)
        from drand_tpu.chain.sqlitedb import SqliteStore
        from drand_tpu.crypto import schemes

        env, traffic = self.env, self.env.traffic
        chunk = env.daemon.sync_chunk
        fx = self.fx = ChainFixture(env.config, traffic, env.seed, chunk)
        scheme = schemes.scheme_from_name(env.config["scheme"])
        self.store = SqliteStore(os.path.join(env.tmp, "chain.db"),
                                 require_previous=scheme.chained)
        warm_hi = min(fx.last, fx.first + int(traffic["warm_rounds"]) - 1)
        t0 = time.monotonic()
        self.store.put_many([Beacon(round=r, signature=s)
                             for r, s in fx.sign(fx.lo, warm_hi)])
        self.tail = fx.sign_elsewhere(warm_hi + 1, fx.last)
        t_head = time.monotonic() - t0
        self.resume = None
        if fx.resume_round is not None:
            self.resume = ScanCheckpoint(
                round=fx.resume_round,
                digest=hashlib.sha256(b"bench-resume").hexdigest(),
                sig_sha=hashlib.sha256(fx.sigs[fx.resume_round]).hexdigest(),
                mode=MODE_FULL)
        proxy = env.verifier(scheme, fx.chain.public_key, fx)
        view = StoreView(self.store, env.window)

        def make_scanner():
            return IntegrityScanner(view, scheme, verifier=proxy, chunk=chunk)

        self.make_scanner = make_scanner
        t_warm = time.monotonic()
        warm = make_scanner().scan(mode=MODE_FULL, upto=warm_hi,
                                   resume=self.resume)
        t_warm = time.monotonic() - t_warm
        t_tail = time.monotonic()
        self.store.put_many([Beacon(round=r, signature=s)
                             for r, s in self.tail.rows()])
        return (f"{fx.rounds} rounds of {fx.scheme_id}, {len(fx.corrupt)} "
                f"planted; warm-up rows signed in {t_head:.1f} s, warm-up "
                f"scan of {warm.scanned} rounds in {t_warm:.1f} s, then "
                f"waited {time.monotonic() - t_tail:.1f} s for the rest of "
                f"the store, signed meanwhile")

    def measure(self) -> str:
        self.reports = scan_until_closed(self.make_scanner, self.resume,
                                         self.env.window)
        return f"{len(self.reports)} scan(s)"

    def close(self) -> None:
        if self.tail is not None:
            self.tail.close()
        if self.store is not None:
            self.store.close()

    def check(self):
        """-> (checks {name: (value, limit)}, rounds compared)."""
        return check.compare(self.env.window, self.reports, self.fx,
                             self.env.seed)
