"""The stored chain of one cell, made from the seed.

A configuration file fixes the scheme, the number of rounds held and the
first round the scan verifies; a traffic file may plant corrupt rows.
Rounds are signed by the reference library with a 1-of-1 key drawn from
the seed, so the same seed gives the same store.

Where the first scanned round F is past genesis, the store also holds the
rows the scanner re-reads to resume there: F-1 (its checkpoint row) and,
for a chained scheme, F-2 (the row that F-1's previous signature is read
from).  The scan then starts at F with a signature-width previous
signature, as a scheduled scan resumes.
"""

import multiprocessing
import os
import random
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor

from . import refbls


class ChainFixture:
    """`chunk` is the scan's chunk: a planted row lies in its block's
    first chunk."""

    def __init__(self, config: dict, traffic: dict, seed: int, chunk: int):
        group, chained, dst = refbls.SCHEMES[config["scheme"]]
        self.config, self.traffic = config, traffic
        self.seed, self.chunk = seed, chunk
        self.scheme_id = config["scheme"]
        self.chain = refbls.Chain(group, chained, dst, seed)
        self.first = int(config["first_round"])
        self.rounds = int(config["rounds"])
        self.last = self.first + self.rounds - 1
        if self.first > 1:
            self.lo = self.first - (2 if chained else 1)
        else:
            self.lo = 1
        self.sigs = {}          # round -> stored signature bytes
        self._true = {}         # round -> the chain's own signature
        rng = random.Random(seed)
        self._prev0 = rng.randbytes(self.chain.sig_len)
        # corrupt plan: one row in each aligned block of the scanned range
        # holds another round's valid signature (right point, wrong
        # message), at an offset drawn from the seed within the block's
        # first chunk: every seed gets the same number of corrupt chunks
        # at the same places in the scan, in other rows
        self.corrupt = {}
        block = int(traffic.get("corrupt_block", 0))
        if block:
            for lo in range(self.first, self.last + 1, block):
                if lo + block - 1 > self.last:
                    break
                r = lo + rng.randrange(min(chunk, block))
                self.corrupt[r] = r - 1 if r - 1 >= self.lo else r + 1

    @property
    def resume_round(self):
        """The checkpoint row the scan resumes after, or None."""
        return self.first - 1 if self.first > 1 else None

    def true_sigs(self, lo: int, hi: int, prev=None) -> list:
        """The chain's own signatures of rounds lo..hi; a chained scheme
        continues from `prev` (the signature of round lo-1)."""
        ch = self.chain
        if ch.chained:
            prev = prev if prev is not None else self._prev0
            out = []
            for r in range(lo, hi + 1):
                prev = ch.sign(r, prev)
                out.append(prev)
            return out
        n = max(1, min(16, (os.cpu_count() or 3) - 2))
        with ThreadPoolExecutor(max_workers=n) as ex:
            return list(ex.map(ch.sign, range(lo, hi + 1), chunksize=256))

    def rows(self, lo: int, sigs: list) -> list:
        """Take the chain's signatures of rounds lo.. and return the stored
        rows [(round, signature)], the corrupt plan applied."""
        for i, s in enumerate(sigs):
            self._true[lo + i] = s
        out = []
        for r in range(lo, lo + len(sigs)):
            donor = self.corrupt.get(r)
            sig = self._true[r] if donor is None else self._true[donor]
            self.sigs[r] = sig
            out.append((r, sig))
        return out

    def sign(self, lo: int, hi: int) -> list:
        """Sign rounds lo..hi here, continuing the chain; -> stored rows."""
        return self.rows(lo, self.true_sigs(lo, hi, self._true.get(lo - 1)))

    def sign_elsewhere(self, lo: int, hi: int):
        """Start signing rounds lo..hi in a child process (it imports no
        JAX), so that the parent can lower and compile meanwhile.  The
        returned object's `rows()` waits for them, and `close()` ends the
        child."""
        return _Elsewhere(self, lo, hi)

    def prev_of(self, round_: int):
        """The previous signature the chain's rule commits round_ to, as
        stored (None for an unchained scheme)."""
        if not self.chain.chained:
            return None
        return self.sigs.get(round_ - 1, self._prev0)


def _true_sigs(config, traffic, seed, chunk, lo, hi, prev):
    return ChainFixture(config, traffic, seed, chunk).true_sigs(lo, hi, prev)


class _Elsewhere:
    def __init__(self, fx: ChainFixture, lo: int, hi: int):
        self.fx, self.lo = fx, lo
        self.pool = ProcessPoolExecutor(
            max_workers=1, mp_context=multiprocessing.get_context("spawn"))
        self.future = self.pool.submit(
            _true_sigs, fx.config, fx.traffic, fx.seed, fx.chunk, lo, hi,
            fx._true.get(lo - 1))

    def rows(self) -> list:
        return self.fx.rows(self.lo, self.future.result())

    def close(self) -> None:
        self.future.cancel()
        self.pool.shutdown(wait=True)
