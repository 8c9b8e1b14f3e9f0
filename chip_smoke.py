#!/usr/bin/env python
"""The daemon's main path on one local TPU chip, end to end.

    python chip_smoke.py                # one chip: phases 1-5
    python chip_smoke.py --four-chips   # four chips: the multi-device path only

One process (the chip belongs to one process at a time), phases in order:

  1. device gate    JAX sees a TPU and the Pallas kernels are the engine.
  2. kernels        every Pallas kernel once at a production width through
                    its public wrapper, against drand_tpu/crypto/host.
  3. catch-up G1    16,384 quicknet rounds (bls-unchained-g1-rfc9380, 1-of-1
                    signer) in a SqliteStore, one signature corrupted,
                    replayed through VerifyService.handle(device=True).
  4. catch-up G2    1,024 rounds of the LoE default scheme
                    (pedersen-bls-chained) with previous_sig linkage.
  5. live daemons   3 DrandDaemons on the device verifier: DKG (t=2, n=3,
                    3 s period), >= 3 rounds over gRPC and REST.

Phases 3-5 also gate the verify service: no failover, every handle healthy
and running on "tpu", and the compiled verify program holds the Pallas
kernels (`tpu_custom_call`).  Every phase is bounded; the first failure
prints its reason and exits 1.  The programs of phases 2-5 compile
concurrently on a thread pool from the start (a cold compile is minutes of
host CPU per program); the phases then run in order.  Timings are
informational: none of them is a benchmark.  The last stdout line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.

Each phase takes its sizes as arguments, so a CPU rehearsal at a tiny
size imports and calls them (JAX_PLATFORMS=cpu, platform="cpu").
"""
# tpu-vet: disable-file=clock  (wall-clock phase timing and deadlines of a
# one-shot smoke run; no beacon schedule logic)
# tpu-vet: disable-file=verifier  (the warm-up compiles the service's own
# programs through BatchBeaconVerifier, and the gate enumerates devices)

import argparse
import concurrent.futures as cf
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import threading
import time

# libtpu logs under /tmp unless told otherwise; this run writes nothing
# outside its checkout and its temp dir
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.abspath(__file__))
T0 = time.monotonic()
BUDGET_S = 1140             # the driver allows 1200 s for the whole run
SEED = 20261015
PAD = 8192                  # the verify service's default coalescing width


class SmokeFailure(Exception):
    """An expected failure: its message is the reason, no traceback."""


def log(msg: str) -> None:
    print(f"[{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Bound:
    """Bound a phase: past `limit` seconds (or the run budget) print the
    reason, dump every thread's stack to stderr and exit 1."""

    def __init__(self, name: str, limit: float):
        self.name = name
        self.limit = max(1.0, min(limit, BUDGET_S - (time.monotonic() - T0)))
        self.timer = None

    def _expire(self):
        import faulthandler
        print(f"FAIL {self.name}: exceeded its bound of {self.limit:.0f} s",
              flush=True)
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        os._exit(1)

    def __enter__(self):
        self.t0 = time.monotonic()
        self.timer = threading.Timer(self.limit, self._expire)
        self.timer.daemon = True
        self.timer.start()
        log(f"{self.name}: start (bound {self.limit:.0f} s)")
        return self

    def __exit__(self, *exc):
        self.timer.cancel()
        if exc[0] is None:
            log(f"{self.name}: passed in {time.monotonic() - self.t0:.1f} s")
        return False


# ---------------------------------------------------------------------------
# Phase 1: device gate
# ---------------------------------------------------------------------------

def phase_device_gate(min_count: int = 1) -> dict:
    try:
        import jax
        devs = jax.devices()
    except Exception as e:
        raise SmokeFailure(f"no TPU: JAX backend init failed: {e}")
    d0 = devs[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    check(d0.platform == "tpu",
          f"no TPU: JAX sees {len(devs)} {d0.platform} device(s) "
          f"({d0.device_kind})")
    check(len(devs) >= min_count,
          f"need {min_count} TPU devices, JAX sees {len(devs)}")
    try:
        from drand_tpu import compile_cache
        from drand_tpu.ops import pallas_field as PF
    except ImportError as e:
        raise SmokeFailure(f"drand_tpu is not importable next to "
                           f"chip_smoke.py: {e}")
    log(f"compile cache: {compile_cache.enable()}")
    log(f"engine: DRAND_TPU_PALLAS={os.environ.get('DRAND_TPU_PALLAS', 'auto')}"
        f" -> {PF.engine_mode()}")
    check(PF.enabled() and PF._use_kernels(),
          f"Pallas kernels are not the engine ({PF.engine_mode()})")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def build_native() -> None:
    """Rebuild the native host library from the committed sources, and
    load that build (never a stale .so left in the tree)."""
    try:
        r = subprocess.run(["make", "-B", "-C", os.path.join(ROOT, "native")],
                           capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise SmokeFailure(f"native build failed: {e}")
    check(r.returncode == 0,
          f"native build failed: {(r.stderr or r.stdout)[-600:]}")
    from drand_tpu.crypto.host import native
    check(native.available(), "native library built but did not load")
    log("native host library rebuilt (make -B -C native) and loaded")


# ---------------------------------------------------------------------------
# Phase 2: every Pallas kernel vs the host golden code
# ---------------------------------------------------------------------------

def _points(group, m, rng):
    from drand_tpu.crypto.host import curve as C
    from drand_tpu.crypto.host.params import R
    g = C.G1 if group == "G1" else C.G2
    return [g.mul(g.gen, rng.randrange(1, R)) for _ in range(m)]


def _tile(enc, lanes, m):
    import jax
    import numpy as np
    idx = np.arange(lanes) % m
    return jax.tree.map(lambda t: t[idx], enc)


def _sample(lanes, k, rng):
    return sorted({0, lanes - 1} | {rng.randrange(lanes) for _ in range(k)})


def _decode(group, out, lanes_idx):
    import jax
    import numpy as np
    from drand_tpu.ops import curve as DC
    sub = jax.tree.map(lambda t: np.asarray(t)[lanes_idx], out)
    return (DC.decode_g1_points if group == "G1"
            else DC.decode_g2_points)(sub)


def kernel_checks(lanes: int, pair_lanes: int, sample: int, seed: int):
    """[(name, width, job)]: each job compiles + runs one kernel through
    its public wrapper and returns (ok, detail)."""
    import jax
    import numpy as np
    from drand_tpu.crypto.host import curve as C
    from drand_tpu.crypto.host import field as HF
    from drand_tpu.crypto.host import pairing as HP
    from drand_tpu.crypto.host.params import P, R, X
    from drand_tpu.ops import curve as DC
    from drand_tpu.ops import limbs as L
    from drand_tpu.ops import pairing as DP
    from drand_tpu.ops import tower as T

    m = min(lanes, 16)           # distinct points, tiled across the lanes

    def pow_job():
        rng = random.Random(seed + 1)
        xs = [rng.randrange(P) for _ in range(lanes)]
        e = (P - 3) // 4
        out = jax.jit(lambda a: L.pow_fixed(a, e))(L.encode_mont(xs))
        idx = _sample(lanes, sample, rng)
        got = L.decode_mont(np.asarray(out)[idx])
        return got == [pow(xs[i], e, P) for i in idx], f"{len(idx)} lanes"

    def pow2_job():
        rng = random.Random(seed + 2)
        xs = [(rng.randrange(P), rng.randrange(P)) for _ in range(lanes)]
        e = (P * P - 9) // 16
        a = (L.encode_mont([x[0] for x in xs]),
             L.encode_mont([x[1] for x in xs]))
        out = jax.jit(lambda a: T.fp2_pow_fixed(a, e))(a)
        idx = _sample(lanes, sample, rng)
        got = T.decode_fp2((np.asarray(out[0])[idx], np.asarray(out[1])[idx]))
        got = list(zip(*got))
        return got == [HF.fp2_pow(xs[i], e) for i in idx], f"{len(idx)} lanes"

    def ladder_var_job(group):
        def job():
            rng = random.Random(seed + 3)
            pts = _points(group, m, rng)
            enc = (DC.encode_g1_points if group == "G1"
                   else DC.encode_g2_points)(pts)
            p = _tile(enc, lanes, m)
            ks = [rng.randrange(1, R) for _ in range(lanes)]
            bits = DC.scalars_to_bits(ks, nbits=256)
            curve = DC.G1_DEV if group == "G1" else DC.G2_DEV
            out = jax.jit(curve.scalar_mul_bits)(p, bits)
            idx = _sample(lanes, sample, rng)
            g = C.G1 if group == "G1" else C.G2
            want = [g.mul(pts[i % m], ks[i]) for i in idx]
            return _decode(group, out, idx) == want, f"{len(idx)} lanes"
        return job

    def ladder_fixed_job(group):
        def job():
            rng = random.Random(seed + 4)
            pts = _points(group, m, rng)
            enc = (DC.encode_g1_points if group == "G1"
                   else DC.encode_g2_points)(pts)
            p = _tile(enc, lanes, m)
            # the production chains: [-x] on G1 (subgroup check),
            # [x] on G2 (subgroup check, cofactor clearing)
            k = -X if group == "G1" else X
            curve = DC.G1_DEV if group == "G1" else DC.G2_DEV
            out = jax.jit(lambda q: curve.scalar_mul_fixed(q, k))(p)
            idx = list(range(m)) + [lanes - 1]
            g = C.G1 if group == "G1" else C.G2
            want = [g.mul(pts[i % m], k) for i in idx]
            return _decode(group, out, idx) == want, f"{len(idx)} lanes"
        return job

    def glv_job(group):
        # G1: 2 x pad lanes (S and H), 64-bit halves, lambda = -x^2;
        # G2: 4 x pad lanes (S, psi S, H, psi H), 32-bit quarters, x^2
        width = 2 * lanes if group == "G1" else 4 * lanes
        nb = 64 if group == "G1" else 32
        lam = (-X * X) % R if group == "G1" else (X * X) % R

        def job():
            rng = random.Random(seed + 5)
            pts = _points(group, m, rng)
            enc = (DC.encode_g1_points if group == "G1"
                   else DC.encode_g2_points)(pts)
            p = _tile(enc, width, m)
            k0 = [rng.randrange(1 << nb) for _ in range(width)]
            k1 = [rng.randrange(1 << nb) for _ in range(width)]
            b0 = DC.scalars_to_bits(k0, nbits=nb)
            b1 = DC.scalars_to_bits(k1, nbits=nb)
            fn = DC.g1_glv_msm_terms if group == "G1" \
                else DC.g2_glv_msm_terms
            out = jax.jit(fn)(p, b0, b1)
            idx = _sample(width, sample, rng)
            g = C.G1 if group == "G1" else C.G2
            want = [g.mul(pts[i % m], (k0[i] + lam * k1[i]) % R)
                    for i in idx]
            return _decode(group, out, idx) == want, f"{len(idx)} lanes"
        return job, width

    def sum_job(group):
        width = lanes if group == "G1" else 2 * lanes

        def job():
            rng = random.Random(seed + 6)
            pts = _points(group, m, rng)
            enc = (DC.encode_g1_points if group == "G1"
                   else DC.encode_g2_points)(pts)
            p = _tile(enc, width, m)
            curve = DC.G1_DEV if group == "G1" else DC.G2_DEV
            out = jax.jit(curve.sum_points)(p)
            got = _decode(group, jax.tree.map(lambda t: t[None], out), [0])
            g = C.G1 if group == "G1" else C.G2
            want = None
            for j in range(m):
                cnt = len(range(j, width, m))
                want = g.add(want, g.mul(pts[j], cnt)) if want \
                    else g.mul(pts[j], cnt)
            return got == [want], f"sum of {width} lanes"
        return job, width

    def pairing_job():
        rng = random.Random(seed + 7)
        ks = [rng.randrange(1, R) for _ in range(pair_lanes)]
        g1s = [C.G1.mul(C.G1.gen, k) for k in ks]
        g2s = [C.G2.mul(C.G2.gen, (k * 7 + 3) % R) for k in ks]
        px = L.encode_mont([p_[0] for p_ in g1s])
        py = L.encode_mont([p_[1] for p_ in g1s])
        qx = (L.encode_mont([q[0][0] for q in g2s]),
              L.encode_mont([q[0][1] for q in g2s]))
        qy = (L.encode_mont([q[1][0] for q in g2s]),
              L.encode_mont([q[1][1] for q in g2s]))
        out = jax.jit(lambda a, b, c, d: DP.final_exponentiation(
            DP.miller_loop(a, b, (c, d))))(px, py, qx, qy)
        dec = T.decode_fp12(out)

        def row(i):
            return tuple(tuple((c0[i], c1[i]) for c0, c1 in c6)
                         for c6 in dec)

        ok = all(row(i) == HP.pairing(g1s[i], g2s[i])
                 for i in range(pair_lanes))
        return ok, f"{pair_lanes} pairings"

    g1_glv, g1_glv_w = glv_job("G1")
    g2_glv, g2_glv_w = glv_job("G2")
    g1_sum, g1_sum_w = sum_job("G1")
    g2_sum, g2_sum_w = sum_job("G2")
    # slowest compiles first
    return [
        ("miller+final_exp", pair_lanes, pairing_job),
        ("ladder_fixed G2", lanes, ladder_fixed_job("G2")),
        ("ladder_var G2", lanes, ladder_var_job("G2")),
        ("sum G2", g2_sum_w, g2_sum),
        ("glv G2", g2_glv_w, g2_glv),
        ("ladder_fixed G1", lanes, ladder_fixed_job("G1")),
        ("ladder_var G1", lanes, ladder_var_job("G1")),
        ("glv G1", g1_glv_w, g1_glv),
        ("sum G1", g1_sum_w, g1_sum),
        ("pow Fp2", lanes, pow2_job),
        ("pow Fp", lanes, pow_job),
    ]


def _trim_heap() -> None:
    """Hand freed compile-time memory back to the OS: a TPU compile peaks
    at ~5 GB of host memory and glibc keeps what it freed in per-thread
    arenas (rehearsal, PR 21: 4.8 GB resident after one compile, 1.9 GB
    after a trim).  Without it a run of concurrent compiles climbed to
    39 of the chip host's 46 GB."""
    import ctypes
    import gc
    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass


def job(fn, *args):
    """A pool job: run, then trim the heap."""
    try:
        return fn(*args)
    finally:
        _trim_heap()


def _timed(check_fn):
    t0 = time.monotonic()
    ok, detail = check_fn()
    return ok, detail, time.monotonic() - t0


def phase_kernels(pool, lanes: int = PAD, pair_lanes: int = 2,
                  sample: int = 16, seed: int = SEED):
    """Submit the kernel jobs; returns a callable that waits for them and
    fails on the first kernel that disagrees with the host."""
    jobs = [(name, w, pool.submit(job, _timed, check_fn))
            for name, w, check_fn in kernel_checks(lanes, pair_lanes, sample,
                                                   seed)]

    def finish():
        for name, width, fut in jobs:
            ok, detail, dt = fut.result()
            log(f"  kernel {name:16s} width={width:6d} {detail}: "
                f"{'equal to host' if ok else 'MISMATCH'} "
                f"(compile+run {dt:.1f} s)")
            check(ok, f"kernel {name} disagrees with crypto/host")
        return len(jobs)

    return finish


# ---------------------------------------------------------------------------
# Phases 3-4: catch-up through the verify service
# ---------------------------------------------------------------------------

def unchained_fixture(scheme_id: str, n: int, chunk: int, seed: int):
    """n rounds signed on device by a 1-of-1 signer (batch.sign_batch, in
    `chunk`-round calls so one signing program serves them all)."""
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.crypto import batch, schemes
    sch = schemes.scheme_from_name(scheme_id)
    sec, pub = sch.keypair(seed=f"smoke-{seed}".encode())
    beacons = []
    for lo in range(1, n + 1, chunk):
        rounds = list(range(lo, min(n, lo + chunk - 1) + 1))
        msgs = [sch.digest_beacon(r, None) for r in rounds]
        msgs += [msgs[-1]] * (chunk - len(msgs))
        sigs = batch.sign_batch(sch, sec, msgs)
        beacons += [Beacon(round=r, signature=s)
                    for r, s in zip(rounds, sigs)]
    return sch, sch.public_bytes(pub), beacons


def chained_fixture(scheme_id: str, n: int, seed: int):
    """Rounds 1..n+1 of a chained scheme, signed in order on the host
    (each message commits to the previous signature); round 1 anchors on
    a 32-byte genesis seed."""
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.crypto import schemes
    sch = schemes.scheme_from_name(scheme_id)
    sec, pub = sch.keypair(seed=f"smoke-{seed}".encode())
    prev = bytes(random.Random(seed).randbytes(32))
    beacons = []
    for r in range(1, n + 2):
        sig = sch.sign(sec, sch.digest_beacon(r, prev))
        beacons.append(Beacon(round=r, signature=sig, previous_sig=prev))
        prev = sig
    return sch, sch.public_bytes(pub), beacons


def host_fixture(scheme_id: str, n: int, seed: int):
    """n rounds signed on the host (native library), chained or not."""
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.crypto import schemes
    sch = schemes.scheme_from_name(scheme_id)
    if sch.chained:
        return chained_fixture(scheme_id, n - 1, seed)
    sec, pub = sch.keypair(seed=f"smoke-{seed}".encode())
    return sch, sch.public_bytes(pub), [
        Beacon(round=r, signature=sch.sign(sec, sch.digest_beacon(r, None)))
        for r in range(1, n + 1)]


def warm_program(scheme_id: str, what: str, pad: int, seed: int) -> float:
    """Compile one program a catch-up replay runs, on a verifier built as
    the service builds its device backends: the RLC check at `pad`
    ("rlc") or the exact leaf of its bisection at 64 lanes ("exact").
    Returns the seconds it took."""
    from drand_tpu.crypto import batch
    t0 = time.monotonic()
    sch, pub, beacons = host_fixture(scheme_id, 65, seed)
    part = beacons[-64:]
    rounds = [b.round for b in part]
    sigs = [b.signature for b in part]
    prevs = [b.previous_sig for b in part]
    ver = batch.BatchBeaconVerifier(sch, pub, pad_to=pad,
                                    h2f_device=batch.h2f_device_default(pad))
    if what == "rlc":
        got = ver.verify_batch(rounds, sigs, prevs)
    else:
        # the leaf of a bisection: a 64-lane slice of the raw encoding
        enc, _bad, front = ver._pack_enc(rounds, sigs, prevs, len(part))
        got = ver._exact(enc, len(part), front=front)
    check(got.all(), f"warm-up {what} verify of good rounds failed")
    return time.monotonic() - t0


def warm_service(scheme_id: str, pad: int, seed: int,
                 platform: str) -> float:
    """Compile the RLC check of `scheme_id` at `pad` through a
    VerifyService, as phase 3 runs it: a cold first call on the service
    thread under the watchdog.  A cold compile runs past the watchdog's
    floor (120 s) and must be spared, not taken for a hung device: a
    trip or a failover fails the phase.  Returns the seconds it took."""
    from drand_tpu.crypto.verify_service import VerifyService
    t0 = time.monotonic()
    sch, pub, beacons = host_fixture(scheme_id, 65, seed)
    part = beacons[-64:]
    svc = VerifyService(pad=pad)
    try:
        got = svc.handle(sch, pub, device=True).verify_batch(
            [b.round for b in part], [b.signature for b in part],
            [b.previous_sig for b in part])
        check(got.all(), f"warm-up {sch.id}: verify of good rounds failed")
        st = gate_service(svc, platform, f"warm-up {sch.id}")
        check(st["watchdog_trips"] == 0, f"warm-up {sch.id}: the "
              f"watchdog tripped {st['watchdog_trips']} times on a compile")
    finally:
        svc.stop()
    dt = time.monotonic() - t0
    log(f"  warm-up {sch.id} RLC through the verify service: {dt:.1f} s "
        f"(watchdog floor {svc.watchdog_floor:.0f} s): 0 trips, "
        "0 failovers")
    return dt


def gate_service(svc, platform: str, what: str) -> dict:
    st = svc.stats()
    check(st["failovers"] == 0, f"{what}: {st['failovers']} failovers")
    bad = {k: v for k, v in st["backends"].items() if v != "healthy"}
    check(not bad, f"{what}: backends not healthy: {bad}")
    off = {k: v for k, v in st["platforms"].items() if v != platform}
    check(not off, f"{what}: handles not on {platform}: {off}")
    return st


def phase_catchup(name: str, fixture, tmp: str, corrupt: int, pad: int,
                  sample: int, platform: str, seed: int = SEED) -> None:
    """Store the fixture in a SqliteStore, replace round `corrupt`'s
    signature with another round's (a valid point over the wrong
    message), replay the store through the verify service and compare
    with the host golden verifier."""
    import numpy as np
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.chain.sqlitedb import SqliteStore
    from drand_tpu.crypto.verify_service import VerifyService

    sch, pub, beacons = fixture
    store = SqliteStore(os.path.join(tmp, f"{name}.db"),
                        require_previous=sch.chained)
    store.put_many([Beacon(round=b.round, signature=b.signature)
                    for b in beacons])
    store.put(Beacon(round=corrupt,
                     signature=beacons[corrupt % len(beacons)].signature))
    first = 2 if sch.chained else 1       # chained: every prev in the store
    rounds, sigs, prevs = [], [], []
    cur = store.cursor()
    b = cur.seek(first)
    while b is not None:
        rounds.append(b.round)
        sigs.append(b.signature)
        prevs.append(b.previous_sig)
        b = cur.next()
    store.close()
    # the successor of a corrupt chained round commits to the corrupt
    # signature: its message is wrong too (the unlinked successor)
    expect = [corrupt, corrupt + 1] if sch.chained else [corrupt]
    log(f"  {name}: {len(rounds)} rounds of {sch.id} replayed from sqlite, "
        f"round {corrupt} corrupted")

    svc = VerifyService(pad=pad)
    try:
        h = svc.handle(sch, pub, device=True)
        t0 = time.monotonic()
        verdict = h.verify_batch(rounds, sigs, prevs)
        dt = time.monotonic() - t0
        flagged = [rounds[i] for i in np.flatnonzero(~verdict)]
        log(f"  {name}: verdict in {dt:.2f} s, flagged {flagged} "
            f"(expected {expect})")
        check(flagged == expect, f"{name}: flagged {flagged}, "
              f"expected {expect}")
        rng = random.Random(seed)
        pick = sorted(set(i for i, r in enumerate(rounds) if r in expect)
                      | set(rng.sample(range(len(rounds)),
                                       min(sample, len(rounds)) - len(expect))))
        host = [sch.verify_beacon(pub, rounds[i], prevs[i], sigs[i])
                for i in pick]
        check(host == [bool(verdict[i]) for i in pick],
              f"{name}: device verdicts differ from the host verifier")
        log(f"  {name}: {len(pick)} sampled verdicts equal the host "
            f"golden verifier")
        st = gate_service(svc, platform, name)
        log(f"  {name}: service dispatches={st['dispatches']} "
            f"failovers={st['failovers']} backends={st['backends']} "
            f"platforms={st['platforms']}")
        if platform == "tpu":
            # the RLC program a chunk dispatches, lowered as it runs (an
            # in-memory cache hit: that shape has just run)
            n, enc, _bad, front = h.backend.pack_chunk(
                rounds[:pad], sigs[:pad], prevs[:pad])
            pipe, args = h.backend._rlc_call(enc, n, front)
            text = pipe.lower(*args).compile().as_text()
            n_calls = text.count("tpu_custom_call")
            check(n_calls > 0, f"{name}: compiled verify program has no "
                  "tpu_custom_call (XLA engine replaced the kernels)")
            log(f"  {name}: compiled RLC program holds {n_calls} "
                "tpu_custom_call sites")
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# Phase 5: live daemons on the device verifier
# ---------------------------------------------------------------------------

def warm_partials(scheme_id: str, n: int, thr: int, seed: int) -> float:
    """Compile the aggregation-time partial-verify program of an n-node
    group: one program, whatever number of partials a round verifies."""
    from drand_tpu.crypto import partials, schemes, tbls
    t0 = time.monotonic()
    sch = schemes.scheme_from_name(scheme_id)
    poly = tbls.PriPoly.random(thr, secret=seed)
    bv = partials.BatchPartialVerifier(sch, poly.commit(sch.key_group), n)
    msg = sch.digest_beacon(2, bytes(96) if sch.chained else None)
    parts = [tbls.sign_partial(sch, s, msg) for s in poly.shares(n)]
    check(bv.verify_partials([msg], [parts[:thr]]).all(),
          "warm-up partial verify failed")
    return time.monotonic() - t0


def _run_dkg(daemons, n: int, thr: int, period: int, timeout: float):
    from drand_tpu.net import ControlClient, convert
    from drand_tpu.protos import drand_pb2 as pb
    secret = b"chip-smoke-dkg"
    leader_addr = daemons[0].gateway.listen_addr
    results = [None] * len(daemons)
    errors = []

    def leader():
        req = pb.InitDKGPacket(
            info=pb.SetupInfo(leader=True, nodes=n, threshold=thr,
                              timeout_seconds=30, secret=secret),
            beacon_period_seconds=period,
            metadata=convert.metadata("default"))
        try:
            results[0] = ControlClient(daemons[0].control.port).stub \
                .init_dkg(req, timeout=timeout)
        except Exception as e:
            errors.append(e)

    def follower(i):
        req = pb.InitDKGPacket(
            info=pb.SetupInfo(leader=False, leader_address=leader_addr,
                              timeout_seconds=30, secret=secret),
            metadata=convert.metadata("default"))
        cc = ControlClient(daemons[i].control.port)
        deadline = time.monotonic() + timeout
        while True:
            try:
                results[i] = cc.stub.init_dkg(req, timeout=timeout)
                return
            except Exception as e:
                if time.monotonic() >= deadline:
                    errors.append(e)
                    return
                time.sleep(0.2)

    threads = [threading.Thread(target=leader)] + [
        threading.Thread(target=follower, args=(i,))
        for i in range(1, len(daemons))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout + 10)
    check(not errors and all(r is not None for r in results),
          f"DKG failed: {errors}")
    groups = [convert.proto_to_group(r) for r in results]
    check(len({g.public_key.key() for g in groups}) == 1,
          "DKG: collective keys differ")
    return groups[0]


def phase_daemons(tmp: str, platform: str, n: int = 3, thr: int = 2,
                  period: int = 3, rounds: int = 3,
                  timeout: float = 240) -> None:
    import urllib.request
    from drand_tpu.core.config import Config
    from drand_tpu.core.daemon import DrandDaemon
    from drand_tpu.http_server import RestServer
    from drand_tpu.net import Peer, ProtocolClient

    daemons, rest = [], None
    try:
        for i in range(n):
            d = DrandDaemon(Config(
                folder=os.path.join(tmp, f"node{i}"), control_port=0,
                private_listen="127.0.0.1:0", dkg_timeout=10,
                dkg_kickoff_grace=0.8, db_engine="sqlite",
                use_device_verifier=True))
            d.start()
            daemons.append(d)
        rest = RestServer(daemons[0], "127.0.0.1:0")
        rest.start()
        group = _run_dkg(daemons, n, thr, period, timeout=120)
        sch = group.scheme
        pub = group.public_key.key()
        log(f"  daemons: DKG done ({sch.id}, t={thr} of n={n}, "
            f"period {period} s)")
        client = ProtocolClient()
        addr = daemons[0].gateway.listen_addr
        deadline = time.monotonic() + timeout
        latest = None
        while time.monotonic() < deadline:
            try:
                latest = client.public_rand(Peer(addr), 0, "default")
                if latest.round >= rounds:
                    break
            except Exception:
                pass
            time.sleep(0.5)
        check(latest is not None and latest.round >= rounds,
              f"daemons: round {rounds} not reached in {timeout:.0f} s")
        for r in range(1, latest.round + 1):
            b = client.public_rand(Peer(addr), r, "default")
            check(sch.verify_beacon(pub, b.round, b.previous_signature or None,
                                    b.signature),
                  f"daemons: gRPC round {r} fails the host verifier")
        log(f"  daemons: rounds 1..{latest.round} over gRPC public_rand "
            "verified by the host golden code")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{rest.port}/public/latest",
                timeout=10) as resp:
            js = json.loads(resp.read())
        prev = bytes.fromhex(js.get("previous_signature", "")) or None
        check(sch.verify_beacon(pub, js["round"], prev,
                                bytes.fromhex(js["signature"])),
              "daemons: REST latest fails the host verifier")
        log(f"  daemons: REST /public/latest round {js['round']} verified "
            "by the host golden code")
        for i, d in enumerate(daemons):
            st = gate_service(d.cfg.verify_service(), platform,
                              f"daemon {i}")
            live = st["calls"]["live"]
            pv = d.processes["default"].handler.chain.partial_verifier
            inner = type(getattr(pv, "inner", None)).__name__
            check(live >= 1 and inner == "DevicePartialVerifier"
                  and getattr(pv, "_fallback", None) is None,
                  f"daemon {i}: partials not verified on the device's live "
                  f"lane (live calls {live}, verifier {inner})")
            log(f"  daemon {i}: {live} live-lane partial verifications on "
                f"the device, failovers={st['failovers']}, "
                f"platforms={st['platforms']}")
    finally:
        if rest is not None:
            rest.stop()
        for d in daemons:
            d.stop()


# ---------------------------------------------------------------------------
# --four-chips: the multi-device path only
# ---------------------------------------------------------------------------

def phase_four_chips(platform: str, n_dev: int = 4, rounds: int = PAD,
                     big: int = 4 * PAD, pad: int = PAD,
                     seed: int = SEED) -> None:
    """A DevicePool in AUTO mode (one group per device) serving one chain
    per device on its own group stream; one `big`-round batch sharded on
    the round axis across the pool; the sharded recover-and-verify step
    of __graft_entry__.dryrun_multichip (t=7 of 13) on a real mesh.
    Compared with the host golden verdicts and with a one-device verifier."""
    import jax
    import numpy as np
    import __graft_entry__
    from drand_tpu.crypto import batch, schemes
    from drand_tpu.crypto.verify_service import VerifyService

    devs = jax.devices()
    check(len(devs) >= n_dev, f"need {n_dev} devices, have {len(devs)}")
    sid = schemes.RFC9380_SCHEME_ID
    chains = [unchained_fixture(sid, rounds, pad, seed + i)
              for i in range(n_dev)]
    svc = VerifyService(pad=pad)
    try:
        handles = [svc.handle(sch, pub, device=True)
                   for sch, pub, _ in chains]
        gids = sorted(h.gid for h in handles)
        check(gids == list(range(n_dev)),
              f"chains not spread one per group: {gids}")
        inputs = []
        for ci, (sch, pub, beacons) in enumerate(chains):
            sigs = [b.signature for b in beacons]
            bad = 1 + 37 * (ci + 1) % (rounds - 1)
            sigs[bad - 1] = sigs[bad]
            inputs.append(([b.round for b in beacons], sigs, bad))
        t0 = time.monotonic()
        futs = [h.submit(r, s, flush_now=True)
                for h, (r, s, _) in zip(handles, inputs)]
        verdicts = [f.result() for f in futs]
        log(f"  {n_dev} chains x {rounds} rounds on {n_dev} group streams "
            f"in {time.monotonic() - t0:.2f} s")
        one = devs[:1]
        for (sch, pub, _), (r, s, bad), v in zip(chains, inputs, verdicts):
            check(list(np.flatnonzero(~v) + 1) == [bad],
                  f"chain verdict flags {list(np.flatnonzero(~v) + 1)}, "
                  f"expected [{bad}]")
            ref = batch.BatchBeaconVerifier(sch, pub, pad_to=pad,
                                            devices=one).verify_batch(r, s)
            check((ref == v).all(), "group verdicts differ from one device")
            for i in (0, bad - 1, len(r) - 1):
                check(sch.verify_beacon(pub, r[i], None, s[i]) == bool(v[i]),
                      "group verdict differs from the host verifier")
        st = svc.stats()
        per_group = {g: st["groups"][g]["dispatches"] for g in st["groups"]}
        check(all(per_group.get(g, 0) > 0 for g in range(n_dev)),
              f"not every device group dispatched: {per_group}")
        log(f"  per-group dispatches {per_group}; verdicts equal the "
            "one-device verifier and the host")

        sch, pub, beacons = unchained_fixture(sid, big, pad, seed + 99)
        r = [b.round for b in beacons]
        s = [b.signature for b in beacons]
        s[big // 2] = s[big // 2 + 1]
        h = svc.handle(sch, pub, device=True)
        t0 = time.monotonic()
        v = h.verify_batch(r, s)
        log(f"  {big}-round batch in {time.monotonic() - t0:.2f} s")
        st = svc.stats()
        check(st["sharded_dispatches"] > 0, "big batch was not sharded")
        slot = svc._slots[h.key]
        devset = {d.id for d in slot.pool_backend._placement().device_set}
        check(len(devset) == n_dev, f"sharded over {sorted(devset)}")
        check(list(np.flatnonzero(~v)) == [big // 2],
              f"sharded verdict flags {list(np.flatnonzero(~v))}")
        ref = batch.BatchBeaconVerifier(sch, pub, pad_to=pad,
                                        devices=one).verify_batch(r, s)
        check((ref == v).all(), "sharded verdicts differ from one device")
        log(f"  sharded over devices {sorted(devset)}: verdicts equal the "
            "one-device verifier")
        gate_service(svc, platform, "four-chip service")
    finally:
        svc.stop()

    out = __graft_entry__.dryrun_multichip(n_dev)
    check(out["ok"].all() and out["recovered"] == out["expect"],
          "sharded recover+verify disagrees with the host signatures")
    check(len(out["devices"]) == n_dev,
          f"recovered batch on devices {out['devices']}")
    log(f"  dryrun_multichip: {len(out['ok'])} rounds recovered (t=7 of 13) "
        f"and verified on devices {out['devices']}, equal to the host")


# ---------------------------------------------------------------------------


def run_one_chip(dev: dict, pool, tmp: str, pad: int = PAD,
                 lanes: int = PAD, n_g1: int = 2 * PAD, n_g2: int = 1024,
                 corrupt_g1: int = 12345, corrupt_g2: int = 700) -> None:
    """Phases 1b-5 at the given sizes (production by default)."""
    from drand_tpu.crypto import schemes

    with Bound("phase 1b native build", 300):
        build_native()

    def timed(fn, *args):
        t0 = time.monotonic()
        return fn(*args), time.monotonic() - t0

    g1, g2 = schemes.RFC9380_SCHEME_ID, schemes.DEFAULT_SCHEME_ID
    # every program the later phases run compiles now on the pool, longest
    # jobs first (chip run order); the phases below then run in order.
    # The quicknet RLC check compiles through a verify service, under its
    # watchdog (warm_service).
    warm = {name: pool.submit(job, fn, *args) for name, fn, *args in (
        ("G2 RLC", warm_program, g2, "rlc", pad, SEED),
        ("partials", warm_partials, g2, 3, 2, SEED),
        ("G2 exact leaf", warm_program, g2, "exact", pad, SEED),
        ("G1 RLC", warm_service, g1, pad, SEED, dev["platform"]),
        ("G1 exact leaf", warm_program, g1, "exact", pad, SEED))}
    fx3 = pool.submit(job, timed, unchained_fixture, g1, n_g1, pad, SEED)
    fx4 = pool.submit(job, timed, chained_fixture, g2, n_g2, SEED)
    finish_kernels = phase_kernels(pool, lanes=lanes)

    def warmed(*names):
        log("  warm-up compile+run: " + ", ".join(
            f"{n} {warm[n].result():.1f} s" for n in names))

    # phase 2 waits on the whole compile pool (its jobs queue last)
    with Bound("phase 2 kernels vs host", BUDGET_S):
        n = finish_kernels()
        log(f"  {n} kernel checks equal to the host golden code")
    for name, fut, corrupt, programs in (
            ("3 catch-up quicknet", fx3, corrupt_g1,
             ("G1 RLC", "G1 exact leaf")),
            ("4 catch-up chained", fx4, corrupt_g2,
             ("G2 RLC", "G2 exact leaf"))):
        with Bound(f"phase {name}", 900):
            warmed(*programs)
            fx, fx_s = fut.result()
            log(f"  fixture: {len(fx[2])} rounds signed in {fx_s:.1f} s")
            phase_catchup(f"catchup-{fx[0].id}", fx, tmp, corrupt=corrupt,
                          pad=pad, sample=64, platform=dev["platform"])
    with Bound("phase 5 live daemons", 900):
        warmed("partials")
        phase_daemons(tmp, dev["platform"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip path (4 devices)")
    args = ap.parse_args(argv)
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    # 4 concurrent compiles: each peaks at ~5 GB of host memory, and the
    # chip host has 46 GB
    pool = cf.ThreadPoolExecutor(max_workers=4)
    try:
        with Bound("phase 1 device gate", 180):
            dev = phase_device_gate(4 if args.four_chips else 1)
        if args.four_chips:
            with Bound("four-chip path", BUDGET_S):
                phase_four_chips(dev["platform"])
        else:
            run_one_chip(dev, pool, tmp)
    except SmokeFailure as e:
        print(f"FAIL: {e}", flush=True)
        return 1
    except Exception as e:
        import traceback
        traceback.print_exc()
        print(f"FAIL: {type(e).__name__}: {e}", flush=True)
        return 1
    finally:
        pool.shutdown(wait=False, cancel_futures=True)
        shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    # pool threads may still be inside a compile after a failure: leave
    # without joining them
    os._exit(rc)
