#!/usr/bin/env python
"""Round benchmark: all five BASELINE.json configs on one chip.

Output protocol (VERDICT r3 #1 — a driver kill must never erase finished
results): after EVERY config the parent prints a FULL cumulative JSON
result line to stdout (flushed).  The last line parses as the round
result whenever the process dies; configs not yet run are null.

The headline metric is config 5 — STREAMED verification of fresh beacons
replayed from a populated SqliteStore with host packing double-buffered
against device compute (BASELINE config 5: the honest end-to-end number,
not a warm re-verify of one resident batch).

The baseline anchor is the serial-CPU figure from BASELINE.md: a single
pairing-based verification is milliseconds-scale on one core, pinned at
500 rounds/sec (reference harness crypto/schemes_test.go:15-45).

Configs (BASELINE.json north_star):
  1. chained_catchup   1k  pedersen-bls-chained rounds (client/verify.go
                       :139-160 walk, batched; linkage checked host-side)
  2. unchained_resident 106,496 (13 x 8192) bls-unchained-on-g1 rounds
                       pre-encoded device-resident, verified in 13
                       same-shaped RLC passes (kernel throughput at the
                       BASELINE-specified 100k scale)
  3. partials_recover  10k rounds x t=7-of-13 in 2048-round chunks:
                       batched partial verify + Lagrange recovery
                       (chainstore.go:202-207), recovered sigs re-verified
  4. mixed_4chains     4 concurrent chains (2 schemes x {chained,
                       unchained} x {G1,G2} mix) verified chunk-interleaved
  5. streamed_store    106,496 rounds (13 x 8192) streamed from
                       SqliteStore, double buffered (the headline; an
                       exact chunk multiple so every chunk shares ONE
                       compiled program shape)
  6. coalesced_service the same replay submitted through the resident
                       verify service in quarter-chunk spans: coalescing
                       merges 4 submissions per PAD-lane dispatch
                       (dispatch counter recorded in stats), double
                       buffering via the service's pipelined executor
  7. multidevice_scaleout (ISSUE 11): one chain per device group served
                       CONCURRENTLY through per-group dispatch streams
                       (per-group throughput recorded), then one huge
                       batch round-axis-sharded across the FULL pool;
                       n_devices/group_map land in the JSON (on a
                       1-device chip this degenerates to one group +
                       an unsharded huge batch — still measured, never
                       marked degraded for that)
  9. multitenant_serving (ISSUE 15): N tenants with heterogeneous
                       schemes (G1 vs G2 cost) served through the
                       tenancy layer — weighted placement, per-tenant
                       read admission (one tenant deliberately
                       rate-capped), measured per-tenant device time;
                       per-tenant r/s, quota rejections and the
                       placement map land in the JSON

Compiled-program economy: every verifier pads to PAD=8192 (pad_to), so
each RLC program shape compiles once.  Since ISSUE 14 the message FRONT
is part of the flavor: configs 5/6 stream the G1-RLC with the
raw-message device-h2f front (message-bytes-in — the steady-state
serving path), config 2's resident re-verify keeps the host-expanded
"fields" front (hash once, re-verify many), config 1's chained chunk
carries the digest front (its genesis slot has a seed-width
previous_sig), and config 4 adds the remaining raw fronts — about
six RLC programs plus partials-verify@(2048x7), the fused
decompress+recover GLV program and the fixture signing pipelines.  All
configs run inside ONE child process so each program compiles (or
cache-loads) at most once; the parent restarts the child for the
remaining configs if it hangs or dies.

Fixture chains are generated once and cached under <checkout>/.bench_cache
(generation is setup, not measurement).  DRAND_TPU_BENCH_CONFIGS=1,5
limits the run; DRAND_TPU_BENCH_N scales config 5.
"""

import json
import os
import sys
import time

BASELINE_RPS = 500.0  # serial kyber CPU anchor (BASELINE.md)
CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_cache")
GENESIS_PREV = b"\x09" * 32  # chained fixture genesis-seed stand-in
PAD = int(os.environ.get("DRAND_TPU_BENCH_PAD", "8192"))
# 13 x 8192: >=100k (BASELINE spec) AND an exact multiple of the chunk so
# the streamed path never compiles a second (tail-sized) program
N_STREAM = int(os.environ.get("DRAND_TPU_BENCH_N", str(13 * PAD)))
N_RESIDENT = int(os.environ.get("DRAND_TPU_BENCH_N_RESIDENT", str(13 * PAD)))
N_CHAINED = int(os.environ.get("DRAND_TPU_BENCH_N_CHAINED", "1024"))
N_PARTIAL_ROUNDS = int(os.environ.get("DRAND_TPU_BENCH_N_PARTIALS", "10240"))
PARTIAL_CHUNK = int(os.environ.get("DRAND_TPU_BENCH_PARTIAL_CHUNK", "2048"))
N_MIXED = int(os.environ.get("DRAND_TPU_BENCH_N_MIXED", "4096"))
# config 7: rounds per chain (2 pad-chunks each) and how many chains at
# most — one per device group, capped so fixture signing stays bounded
N_MD = int(os.environ.get("DRAND_TPU_BENCH_N_MD", str(2 * PAD)))
MD_MAX_CHAINS = int(os.environ.get("DRAND_TPU_BENCH_MD_CHAINS", "4"))
CHUNK = int(os.environ.get("DRAND_TPU_BENCH_CHUNK", str(PAD)))
# config 8 (ISSUE 13): committee size for the in-process Handel
# aggregation + device-DKG measurements; rounds timed after warmup.
# The signing-fixture and host-commit setup scale with COMMITTEE_N, so
# CPU smokes should set DRAND_TPU_BENCH_COMMITTEE_N=64 or so.
COMMITTEE_N = int(os.environ.get("DRAND_TPU_BENCH_COMMITTEE_N", "1024"))
COMMITTEE_ROUNDS = int(os.environ.get("DRAND_TPU_BENCH_COMMITTEE_ROUNDS",
                                      "4"))
COMMITTEE_DKG_T = int(os.environ.get("DRAND_TPU_BENCH_COMMITTEE_T", "32"))
# config 9 (ISSUE 15): rounds per tenant replay, timed passes, and how
# many tenants at most (heterogeneous-scheme lineup is defined in the
# config; trimming it trims from the tail)
N_TENANT = int(os.environ.get("DRAND_TPU_BENCH_TENANT_N", str(2 * PAD)))
TENANT_PASSES = int(os.environ.get("DRAND_TPU_BENCH_TENANT_PASSES", "2"))
TENANT_MAX = int(os.environ.get("DRAND_TPU_BENCH_TENANT_MAX", "4"))


def _progress(msg):
    """Child -> parent heartbeat: config 3 is a chain of several big cold
    compiles (the partials-verify program alone is tens of minutes on a
    cold CPU cache), and the parent's no-progress watchdog must not kill
    a config that is legitimately still compiling its next stage."""
    print(json.dumps({"progress": msg}), flush=True)


def _configs():
    raw = os.environ.get("DRAND_TPU_BENCH_CONFIGS", "1,2,3,4,5,6,7,8,9")
    out = set()
    for x in raw.split(","):
        x = x.strip()
        if x.isdigit() and 1 <= int(x) <= 9:
            out.add(int(x))
    return out or {1, 2, 3, 4, 5, 6, 7, 8, 9}


def _jax_setup():
    """Child start-up: the compile cache, then a fail-fast device check —
    a child that finds no chip exits with `{"fatal": ...}` and the parent
    stops; it never measures on a fallback platform.
    DRAND_TPU_BENCH_PLATFORM names another expected platform (CPU smoke
    tests of the bench protocol; the parent passes it as JAX_PLATFORMS)."""
    import jax

    from drand_tpu import compile_cache

    compile_cache.enable()
    want = os.environ.get("DRAND_TPU_BENCH_PLATFORM", "tpu")
    dev = jax.devices()[0]
    if dev.platform != want:
        print(json.dumps({"fatal": f"expected a {want} device, JAX has "
                                   f"{dev.platform} ({dev.device_kind})"}),
              flush=True)
        sys.exit(3)


# ---------------------------------------------------------------------------
# Fixture generation (cached; setup is NOT timed)
# ---------------------------------------------------------------------------

def _store_path(tag):
    os.makedirs(CACHE, exist_ok=True)
    return os.path.join(CACHE, f"{tag}.db")


def _unchained_store(scheme_id, n, seed, tag):
    """SqliteStore with n device-signed unchained beacons (cached)."""
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.chain.sqlitedb import SqliteStore
    from drand_tpu.crypto import batch, schemes

    sch = schemes.scheme_from_name(scheme_id)
    sec, pub = sch.keypair(seed=seed)
    path = _store_path(f"{tag}-{seed.hex()}-{n}")
    store = SqliteStore(path)
    if len(store) >= n:
        return sch, sch.public_bytes(pub), store
    rounds = list(range(len(store) + 1, n + 1))
    for lo in range(0, len(rounds), CHUNK):
        part = rounds[lo:lo + CHUNK]
        msgs = [sch.digest_beacon(r, None) for r in part]
        sigs = batch.sign_batch(sch, sec, msgs)
        for r, s in zip(part, sigs):
            store.put(Beacon(round=r, signature=s))
        if (lo // CHUNK) % 32 == 0:
            # heartbeat: a multi-million-round fixture (the 3M replay)
            # signs for longer than the parent's no-progress watchdog
            _progress(f"fixture {tag}: {lo + len(part)}/{len(rounds)}")
    return sch, sch.public_bytes(pub), store


def _chained_chain(n):
    """Sequentially-signed chained chain (cached on disk as a store)."""
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.chain.sqlitedb import SqliteStore
    from drand_tpu.crypto import schemes

    sch = schemes.scheme_from_name(schemes.DEFAULT_SCHEME_ID)
    sec, pub = sch.keypair(seed=b"bench-chained")
    path = _store_path(f"chained-{n}")
    store = SqliteStore(path, require_previous=True)
    beacons = []
    if len(store) >= n:
        cur = store.cursor()
        b = cur.first()
        while b is not None:
            beacons.append(b)
            b = cur.next()
        # round 1's previous_sig is the genesis seed, which the trimmed
        # store cannot reconstruct (no round 0) — restore it
        from drand_tpu.chain.beacon import Beacon as _B
        beacons[0] = _B(round=beacons[0].round,
                        signature=beacons[0].signature,
                        previous_sig=GENESIS_PREV)
        return sch, sch.public_bytes(pub), beacons
    prev = GENESIS_PREV
    for r in range(1, n + 1):
        msg = sch.digest_beacon(r, prev)
        sig = sch.sign(sec, msg)
        b = Beacon(round=r, signature=sig, previous_sig=prev)
        store.put(b)
        beacons.append(b)
        prev = sig
    return sch, sch.public_bytes(pub), beacons


def _verifier(sch, pub):
    from drand_tpu.crypto import batch

    return batch.BatchBeaconVerifier(sch, pub, pad_to=PAD)


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------

def bench_chained_catchup(stats):
    sch, pub, beacons = _chained_chain(N_CHAINED)
    ver = _verifier(sch, pub)
    t0 = time.perf_counter()
    ok, _ = ver.verify_chain(beacons)         # warm/compile
    warm = time.perf_counter() - t0
    assert ok
    t0 = time.perf_counter()
    ok, _ = ver.verify_chain(beacons)
    dt = time.perf_counter() - t0
    assert ok
    # the G2-RLC program's first-call cost minus its steady-state cost is
    # (approximately) the compile/cache-load time — the r3 blocker was a
    # >90-min G2 cold compile; this records what it is now (VERDICT r4 #4)
    stats["g2_compile_s"] = round(warm - dt, 1)
    return len(beacons) / dt


def bench_unchained_resident():
    """Device-resident RLC throughput at the BASELINE-specified scale
    (config 2: 100k rounds -> 13 x 8192 = 106,496, an exact multiple of
    the canonical pad so every chunk shares ONE compiled program).  All
    chunks are encoded up front (setup, untimed) and stay resident; the
    timed region is pure device verification passes."""
    import jax

    from drand_tpu.crypto import schemes

    sch, pub, store = _unchained_store(
        schemes.SHORT_SIG_SCHEME_ID, N_RESIDENT, b"drand-tpu-bench", "g1")
    ver = _verifier(sch, pub)

    encs = []
    for lo in range(0, N_RESIDENT, PAD):
        rounds = list(range(lo + 1, min(lo + PAD, N_RESIDENT) + 1))
        sigs = [store.get(r).signature for r in rounds]
        msgs = [sch.digest_beacon(r, None) for r in rounds]
        enc, bad = ver._encode(sigs, msgs, PAD)   # ragged tail pads inert
        assert not bad.any()
        # pre-shard in SETUP so multi-device timed passes do no layout
        # moves (single chip: no-op); later device_puts to the same
        # sharding are then cheap no-transfers
        enc = ver._shard_round_axis(enc)
        jax.block_until_ready(enc)
        encs.append((enc, len(rounds)))
    ok = ver._rlc_ok(*encs[0])                    # warm/compile
    assert ok
    t0 = time.perf_counter()
    oks = [ver._rlc_ok(enc, n) for enc, n in encs]
    dt = time.perf_counter() - t0
    assert all(oks)
    return N_RESIDENT / dt


def bench_partials_recover():
    from drand_tpu.crypto import batch, schemes, tbls
    from drand_tpu.crypto.partials import BatchPartialVerifier

    t, n_nodes = 7, 13
    sch = schemes.scheme_from_name(schemes.SHORT_SIG_SCHEME_ID)
    poly = tbls.PriPoly.random(t, secret=0xBE7C4)
    shares = poly.shares(n_nodes)
    pub_poly = poly.commit(sch.key_group)
    nr, ck = N_PARTIAL_ROUNDS, PARTIAL_CHUNK
    msgs = [sch.digest_beacon(r, None) for r in range(1, nr + 1)]
    # t partials per round from signers 0..t-1 (device-signed per signer,
    # in chunk-sized batches so signing shares the ck-shaped program)
    per_signer = []
    for j in range(t):
        sigs = []
        for lo in range(0, nr, ck):
            sigs.extend(batch.sign_batch(sch, shares[j].value,
                                         msgs[lo:lo + ck]))
        per_signer.append(sigs)
    rows = [[j.to_bytes(2, "big") + per_signer[j][r] for j in range(t)]
            for r in range(nr)]
    raw_grid = [[per_signer[j][r] for j in range(t)] for r in range(nr)]

    bpv = BatchPartialVerifier(sch, pub_poly, n_nodes)

    def run(heartbeat=False):
        out = []
        for lo in range(0, nr, ck):
            grid = raw_grid[lo:lo + ck]       # ragged final chunk: size
            okm = bpv.verify_partials(msgs[lo:lo + ck], rows[lo:lo + ck])
            assert okm.all()
            if heartbeat:                     # partials program compiled
                _progress("partials_verify compiled")
            out.extend(batch.recover_batch(
                sch, [list(range(t))] * len(grid), grid))
            if heartbeat:
                _progress("recover compiled")
                heartbeat = False
        return out

    sigs = run(heartbeat=True)                 # warm/compile
    t0 = time.perf_counter()
    sigs = run()
    dt = time.perf_counter() - t0
    # recovered signatures must verify against the collective key
    _progress("timed; re-verifying recovered sigs vs collective key")
    ver = _verifier(sch, sch.key_group.to_bytes(pub_poly.public_key()))
    for lo in range(0, nr, ck):
        part = sigs[lo:lo + ck]
        assert ver.verify_batch(list(range(lo + 1, lo + 1 + len(part))),
                                part).all()
    return nr / dt


def bench_mixed_4chains():
    from drand_tpu.crypto import schemes

    chains = []
    sch, pub, beacons = _chained_chain(N_CHAINED)
    chains.append((_verifier(sch, pub), beacons))
    for scheme_id, tag in ((schemes.UNCHAINED_SCHEME_ID, "g2u"),
                           (schemes.SHORT_SIG_SCHEME_ID, "g1"),
                           (schemes.SHORT_SIG_SCHEME_ID, "g1b")):
        s, p, store = _unchained_store(scheme_id, N_MIXED, tag.encode(), tag)
        bs = [store.get(r) for r in range(1, N_MIXED + 1)]
        chains.append((_verifier(s, p), bs))

    def run_all():
        total = 0
        for ver, bs in chains:
            ok, _ = ver.verify_chain(bs)
            assert ok
            total += len(bs)
        return total

    total = run_all()                          # warm/compile
    t0 = time.perf_counter()
    total = run_all()
    dt = time.perf_counter() - t0
    return total / dt


def bench_streamed_store(stats):
    from drand_tpu.crypto import schemes

    sch, pub, store = _unchained_store(
        schemes.SHORT_SIG_SCHEME_ID, N_STREAM, b"drand-tpu-bench-stream",
        "g1stream")
    ver = _verifier(sch, pub)
    # effective dispatch-pipeline depth (DRAND_VERIFY_PIPELINE_DEPTH,
    # clamped by the per-chunk footprint budget)
    stats["streamed_depth"] = ver.pipeline_depth(None, CHUNK)

    def replay():
        def it():
            cur = store.cursor()
            b = cur.first()
            while b is not None:
                yield b
                b = cur.next()
        n = 0
        for rounds, ok in ver.verify_stream(it(), chunk_size=CHUNK):
            assert ok.all()
            n += len(rounds)
        return n

    from drand_tpu.crypto import batch as _batch

    t0 = time.perf_counter()
    n = replay()                               # cold (incl. compile/cache)
    stats["streamed_cold_s"] = round(time.perf_counter() - t0, 1)
    pack0 = _batch.pack_seconds()
    t0 = time.perf_counter()
    n = replay()                               # warm steady-state
    dt = time.perf_counter() - t0
    assert n == N_STREAM
    # host pack seconds over the warm replay (ISSUE 14): the term the
    # device hash-to-field front removes the per-message hashing from
    stats["streamed_pack_s"] = round(_batch.pack_seconds() - pack0, 2)
    stats["streamed_h2f_device"] = bool(
        ver.h2f_device if ver.h2f_device is not None
        else _batch.h2f_device_default(PAD))
    return n / dt


def bench_coalesced_service(stats):
    """Config 6 (ISSUE 6): the same streamed replay as config 5, but
    submitted through the resident verify service in quarter-chunk spans
    from a consumer's point of view — the service coalesces them back
    into PAD-lane dispatches, double-buffers host packing against device
    compute, and the dispatch counter proves the reduction (4 submissions
    coalesce per device dispatch)."""
    from drand_tpu.crypto import schemes
    from drand_tpu.crypto.verify_service import VerifyService

    sch, pub, store = _unchained_store(
        schemes.SHORT_SIG_SCHEME_ID, N_STREAM, b"drand-tpu-bench-stream",
        "g1stream")                            # config 5's fixture, shared
    svc = VerifyService(pad=PAD, background_window=0.01)
    handle = svc.handle(sch, pub)
    sub = max(1, PAD // 4)

    def replay():
        futs = []
        buf_rounds, buf_sigs = [], []
        cur = store.cursor()
        b = cur.first()
        while b is not None:
            buf_rounds.append(b.round)
            buf_sigs.append(b.signature)
            if len(buf_rounds) == sub:
                futs.append(handle.submit(buf_rounds, buf_sigs))
                buf_rounds, buf_sigs = [], []
            b = cur.next()
        if buf_rounds:
            futs.append(handle.submit(buf_rounds, buf_sigs))
        n = 0
        for f in futs:
            ok = f.result()
            assert ok.all()
            n += len(ok)
        return n, len(futs)

    try:
        n, _ = replay()                        # cold (compile/cache-load)
        _progress("coalesced_service warm")
        before = svc.stats()
        t0 = time.perf_counter()
        n, submissions = replay()
        dt = time.perf_counter() - t0
        assert n == N_STREAM
        st = svc.stats()
        stats["coalesced_submissions"] = submissions
        stats["coalesced_dispatches"] = st["dispatches"] - \
            before["dispatches"]
        # occupancy observability (ISSUE 10): effective in-flight depth
        # and the queue-time vs device-time split over the warm replay
        stats["coalesced_inflight_depth"] = st["inflight_depth_max"]
        stats["coalesced_pack_s"] = round(
            st["pack_time_s"] - before["pack_time_s"], 2)
        stats["coalesced_queue_s"] = round(
            st["queue_time_s"] - before["queue_time_s"], 2)
        stats["coalesced_device_s"] = round(
            st["device_time_s"] - before["device_time_s"], 2)
        stats["coalesced_tuning"] = st["tuning"]
        # delta'd over the WARM replay only (cumulative stats would blend
        # the cold run's interleaving in)
        slots = st["dispatch_slots"] - before["dispatch_slots"]
        stats["coalesced_fill_ratio"] = round(
            (st["dispatch_lanes"] - before["dispatch_lanes"]) /
            max(1, slots), 3)
        # which backend actually served: a failover mid-run means these
        # numbers are HOST numbers — the r04 silent-zero must never be
        # misread as a device figure again
        stats["coalesced_service_backend"] = (
            "host_fallback" if st["failovers"] > before["failovers"]
            or "degraded" in st["backends"].values()
            or "probing" in st["backends"].values() else "device")
        if st["watchdog_trips"] > before["watchdog_trips"]:
            stats["coalesced_watchdog_trips"] = (
                st["watchdog_trips"] - before["watchdog_trips"])
        return n / dt
    finally:
        svc.stop()


def bench_multidevice_scaleout(stats):
    """Config 7 (ISSUE 11): the device pool on the serving path.  One
    chain per device group, submitted CONCURRENTLY through the service's
    per-group dispatch streams (per-group throughput + the concurrency
    proof recorded), then one huge batch whose single submission crosses
    the shard threshold and round-axis-shards across the FULL pool.  On
    a 1-device chip the pool degenerates to one group and the huge batch
    runs unsharded — still measured, and NOT a degraded run."""
    import threading

    from drand_tpu.crypto import schemes
    from drand_tpu.crypto.verify_service import VerifyService

    # AUTO shard threshold (pad x max(2, n_devices)): the per-group
    # replays below submit half-fixture spans that stay UNDER it, the
    # huge batch is sized exactly AT it — so the sharded dispatch is a
    # full pool-wide chunk, not mostly pad slots.  The watchdog floor is
    # raised to compile scale — config 7's group- and pool-pinned
    # programs are FRESH compile flavors (placement lands in the
    # executable cache key), and a cold compile tripping the watchdog
    # would silently turn this into a host measurement (the backend
    # self-report below would catch it, but the bench should measure
    # the device, not the failover)
    svc = VerifyService(pad=PAD, background_window=0.0,
                        watchdog_floor=3600.0)
    try:
        n_groups = 1
        chains = []
        for i in range(MD_MAX_CHAINS):
            sch, pub, store = _unchained_store(
                schemes.SHORT_SIG_SCHEME_ID, N_MD,
                f"md-{i}".encode(), f"md{i}")
            handle = svc.handle(sch, pub)
            if i == 0:
                n_groups = svc.stats()["n_groups"]
            chains.append((handle, store))
            if len(chains) >= n_groups:
                break       # one chain per group is the point
        _progress(f"multidevice fixtures ready: {len(chains)} chains "
                  f"over {n_groups} groups")

        def replay(handle, store, n_rounds=N_MD, split=2):
            """One replay of `n_rounds`.  The per-group phases submit in
            `split` under-threshold spans so they measure the GROUP
            stream; the huge-batch phase submits ONE threshold-sized
            span (split=1), deliberately crossing into the pool-wide
            sharded path."""
            rounds = list(range(1, n_rounds + 1))
            sigs = [store.get(r).signature for r in rounds]
            step = (n_rounds + split - 1) // split
            futs = [handle.submit(rounds[lo:lo + step], sigs[lo:lo + step],
                                  lane="live", flush_now=True)
                    for lo in range(0, n_rounds, step)]
            n = 0
            for f in futs:
                ok = f.result()
                assert ok.all()
                n += len(ok)
            return n

        for handle, store in chains:        # warm/compile, serial
            replay(handle, store)
        _progress("multidevice warm; timing concurrent per-group replay")
        per_group = {}
        errs = []

        def worker(handle, store):
            try:
                t0 = time.perf_counter()
                n = replay(handle, store)
                per_group[svc._slots[handle.key].label] = round(
                    n / (time.perf_counter() - t0), 1)
            except Exception as e:          # surfaced after join
                errs.append(e)

        before = svc.stats()
        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker, args=c) for c in chains]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if errs:
            raise errs[0]
        st = svc.stats()
        total = len(chains) * N_MD / dt

        # the huge-batch half: ONE submission sized pad x max(2,
        # n_devices) — exactly the AUTO shard threshold, i.e. one FULL
        # pool-wide chunk, so the sharded number measures the pool and
        # not pad slots.  (pool_sharding permitting; 1 device =
        # unsharded, recorded.)  The fixture store extends on demand.
        h0, _ = chains[0]
        huge_n = PAD * max(2, st["n_devices"])
        _, _, store0 = _unchained_store(
            schemes.SHORT_SIG_SCHEME_ID, huge_n, b"md-0", "md0")
        _progress(f"multidevice huge batch: {huge_n} rounds")
        replay(h0, store0, huge_n, split=1)     # warm the pool program
        t0 = time.perf_counter()
        n = replay(h0, store0, huge_n, split=1)
        sharded_dt = time.perf_counter() - t0
        st2 = svc.stats()
        stats["multidevice_huge_n"] = huge_n
        stats["multidevice_n_devices"] = st2["n_devices"]
        stats["multidevice_n_groups"] = st2["n_groups"]
        stats["multidevice_group_map"] = st2["group_map"]
        stats["multidevice_per_group_rps"] = per_group
        stats["multidevice_concurrent_streams"] = \
            st2["concurrent_streams_max"]
        stats["multidevice_sharded"] = \
            st2["sharded_dispatches"] > st["sharded_dispatches"]
        stats["multidevice_sharded_rps"] = round(n / sharded_dt, 1)
        stats["multidevice_migrations"] = st2["migrations"]
        # self-report the serving backend like config 6: a mid-run
        # failover means these are HOST numbers
        stats["multidevice_scaleout_backend"] = (
            "host_fallback" if st2["failovers"] > before["failovers"]
            or "degraded" in st2["backends"].values()
            or "probing" in st2["backends"].values() else "device")
        return total
    finally:
        svc.stop()


def bench_multitenant_serving(stats):
    """Config 9 (ISSUE 15): N tenants with heterogeneous schemes (G1 vs
    G2 partial cost) and periods, served through the TENANCY layer — the
    registry's weighted placement assigns each tenant's chain a device
    group, every read admission runs the per-tenant sub-budgets, and the
    verify service attributes measured device time per tenant.  Recorded:
    per-tenant rounds/s, quota rejections (one tenant is deliberately
    rate-capped), the chain→group placement map, and per-tenant device
    seconds.  Value = total verified rounds/s across tenants.

    Since PR 19 the lanes also exercise the identity plane: every
    tenant but one presents a real macaroon-style bearer token
    (core/authz.py) that is verified before each span's admission —
    the same check the REST/gRPC edges run — and the remaining lane
    stays anonymous, so `multitenant_authenticated` records both read
    paths side by side."""
    import shutil
    import tempfile
    import threading

    from drand_tpu.core.authz import TokenAuthority
    from drand_tpu.core.tenancy import TenantConfig, TenantRegistry
    from drand_tpu.crypto import schemes
    from drand_tpu.crypto.verify_service import VerifyService
    from drand_tpu.net.admission import AdmissionController, CLASS_SHEDDABLE

    registry = TenantRegistry()     # in-memory: bench, not a daemon
    ctrl = AdmissionController(tenancy=registry, capacity=64,
                               critical_reserve=8)
    svc = VerifyService(pad=PAD, background_window=0.0,
                        watchdog_floor=3600.0)
    svc.set_tenancy(registry)
    # heterogeneous tenants: scheme changes per-round device cost
    # (G1 vs G2 RLC flavors), period is the nominal read cadence the
    # rate quota is sized against; "capped" gets a bucket far below its
    # offered load so quota rejections are measured, not hypothetical
    tenants = [
        ("anchor", schemes.SHORT_SIG_SCHEME_ID, dict(weight=2.0,
                                                     anti_affinity=True)),
        ("burst", schemes.SHORT_SIG_SCHEME_ID, dict(weight=1.0)),
        ("heavy-g2", schemes.UNCHAINED_SCHEME_ID, dict(weight=1.0)),
        ("capped", schemes.SHORT_SIG_SCHEME_ID, dict(weight=0.5, rate=4.0,
                                                     burst=4)),
    ][:max(2, TENANT_MAX)]
    periods = {"anchor": 3, "burst": 30, "heavy-g2": 30, "capped": 30}
    authority_dir = tempfile.mkdtemp(prefix="drand-bench-authz-")
    authority = TokenAuthority(authority_dir)
    lane_tokens = {}
    chains = {}
    for name, scheme_id, kw in tenants:
        chain_id = f"{name}-chain"
        registry.set_tenant(TenantConfig(name=name, chains=(chain_id,),
                                         **kw))
        sch, pub, store = _unchained_store(
            scheme_id, N_TENANT, f"mt-{name}".encode(),
            f"mt-{name}")
        registry.register_chain(chain_id, pk=pub)
        chains[name] = (svc.handle(sch, pub), store)
        # every lane but "capped" reads with a real bearer token; the
        # capped lane stays anonymous so both paths are measured
        if name != "capped":
            lane_tokens[name], _ = authority.mint(name, chains=(chain_id,))
    _progress(f"multitenant fixtures ready: {len(chains)} tenants "
              f"({len(lane_tokens)} token-bearing)")

    def replay(name, count_sheds=False):
        handle, store = chains[name]
        rounds = list(range(1, N_TENANT + 1))
        sigs = [store.get(r).signature for r in rounds]
        step = max(1, N_TENANT // 4)
        served = sheds = 0
        futs = []
        token = lane_tokens.get(name)
        for lo in range(0, N_TENANT, step):
            # authenticated lanes verify their token before every
            # span's admission — the exact order the edges use
            # (token check BEFORE quota spend)
            if token is not None:
                v = authority.verify(token, chain=f"{name}-chain")
                assert v.ok and v.tenant == name, v
            # every span is admitted AS the tenant (the serving-path
            # read admission the REST/gRPC edges perform)
            ticket, s = ctrl.try_admit(CLASS_SHEDDABLE, tenant=name)
            if ticket is None:
                sheds += 1
                assert s.tenant == name and s.retry_after > 0
                continue
            try:
                futs.append((handle.submit(
                    rounds[lo:lo + step], sigs[lo:lo + step],
                    lane="live", flush_now=True), lo, step))
            finally:
                ticket.release()
        for f, lo, _ in futs:
            ok = f.result()
            assert ok.all()
            served += len(ok)
        return served, sheds

    try:
        for name, _, _ in tenants:          # warm/compile, serial
            replay(name)
            _progress(f"multitenant warm: {name}")
        per_tenant = {}
        rejections = {}
        served_total = {}
        errs = []

        def worker(name):
            try:
                t0 = time.perf_counter()
                total_served = total_shed = 0
                for _ in range(TENANT_PASSES):
                    served, sheds = replay(name)
                    total_served += served
                    total_shed += sheds
                dt = time.perf_counter() - t0
                per_tenant[name] = round(total_served / dt, 1)
                rejections[name] = total_shed
                served_total[name] = total_served
            except Exception as e:
                errs.append(e)

        before = svc.stats()
        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker, args=(name,))
                   for name, _, _ in tenants]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if errs:
            raise errs[0]
        st = svc.stats()
        total_rounds = sum(served_total.values())
        stats["multitenant_n_tenants"] = len(tenants)
        stats["multitenant_rounds_per_tenant"] = N_TENANT * TENANT_PASSES
        stats["multitenant_schemes"] = {n: s for n, s, _ in tenants}
        stats["multitenant_periods"] = {n: periods[n]
                                        for n, _, _ in tenants}
        stats["multitenant_per_tenant_rps"] = per_tenant
        stats["multitenant_quota_rejections"] = rejections
        stats["multitenant_authenticated"] = {
            n: n in lane_tokens for n, _, _ in tenants}
        stats["multitenant_placement"] = {
            st["tenant_map"].get(label, "?"): gid
            for label, gid in st["group_map"].items()}
        stats["multitenant_device_seconds"] = {
            n: round(registry.device_seconds_total(n), 3)
            for n, _, _ in tenants}
        stats["multitenant_serving_backend"] = (
            "host_fallback" if st["failovers"] > before["failovers"]
            or "degraded" in st["backends"].values() else "device")
        # the capped tenant must actually have been rate-limited — a
        # zero here means the quota plumbing silently did nothing
        if any(n == "capped" for n, _, _ in tenants) \
                and rejections.get("capped", 0) == 0:
            stats["multitenant_warning"] = "capped tenant was never shed"
        return total_rounds / dt
    finally:
        svc.stop()
        shutil.rmtree(authority_dir, ignore_errors=True)


def bench_committee_scale(stats):
    """Config 8 (ISSUE 13): the committee-scale engine, in-process.

    (a) n=COMMITTEE_N Handel aggregation: one observed node's session
        per round is fed ideal-honest candidate aggregates for every
        tree level, and the whole committee's partials verify in the
        session's ONE windowed `verify` call per round — the
        (1, n)-shaped partials RLC program, so aggregating a
        thousand-signer round costs one dispatch, not n pairings.
        Timed after a warmup round compiles the program; value =
        aggregation rounds/s.
    (b) device DKG share verification at the same n: dispatch count and
        wall time for the full bundle-set check plus the reshare
        constant-term pin (the <= 4 dispatch acceptance, self-reported
        in stats).
    """
    from drand_tpu.beacon import handel as HD
    from drand_tpu.beacon.chainstore import DevicePartialVerifier
    from drand_tpu.crypto import dkg_device, schemes, tbls
    from drand_tpu.crypto.host.params import R as _R
    import random as _random

    n, rounds = COMMITTEE_N, COMMITTEE_ROUNDS
    rng = _random.Random(0xC0117EE)
    sch = schemes.scheme_from_name(schemes.SHORT_SIG_SCHEME_ID)
    # polynomial degree decoupled from the protocol threshold (recovery
    # interpolates from any >= t shares); keeps host setup bounded
    poly = tbls.PriPoly([rng.randrange(_R) for _ in range(8)])
    pub_poly = poly.commit(sch.key_group)
    thr = n // 2 + 1
    _progress(f"committee fixture: signing {n} partials x "
              f"{rounds + 1} rounds")
    shares = [poly.eval(i).value for i in range(n)]
    from drand_tpu.crypto import batch as _batch
    per_round = []
    for r in range(1, rounds + 2):      # +1 warmup round
        msg = sch.digest_beacon(r, None)
        # one msg under n different keys does not batch on-device; the
        # host (native) signer is the fixture generator, not measured
        per_round.append((msg, {i: i.to_bytes(2, "big")
                                + sch.sign(shares[i], msg)
                                for i in range(n)}))

    verifier = DevicePartialVerifier(sch, pub_poly, n)
    levels = HD.num_levels(n)
    cfg = HD.HandelConfig(min_group=2, fanout=4, window=2 * levels + 2,
                          bad_limit=3)

    def one_round(r, msg, partials):
        done = {}
        sess = HD.HandelSession(cfg, n, 0, thr, r, None, msg, verifier,
                                send=lambda *a: None,
                                on_complete=lambda p: done.update(p))
        sess.add_own(partials[0])
        # ideal-honest peers: one full-side candidate per level, all
        # delivered before the tick so the window coalesces the whole
        # committee into one verify call
        for level in range(1, levels + 1):
            block = HD.level_block(n, 0, level)
            sender = block[0]
            side = HD.own_block(n, sender, level)
            sess.receive(level, sender,
                         HD.Aggregate({i: partials[i] for i in side}))
        sess.tick()
        assert len(sess.verified) == n and len(done) >= thr
        return done

    before_d = _batch.dispatch_count()
    one_round(1, *per_round[0])                 # warmup/compile
    _progress("committee aggregation program compiled")
    warm_dispatches = _batch.dispatch_count() - before_d
    t0 = time.perf_counter()
    for r in range(rounds):
        one_round(r + 2, *per_round[r + 1])
    dt = time.perf_counter() - t0
    stats["committee_scale_n"] = n
    stats["committee_scale_levels"] = levels
    stats["committee_scale_dispatches_per_round"] = warm_dispatches
    stats["committee_scale_agg_rounds_per_s"] = round(rounds / dt, 3)

    # (b) device DKG share-verify at n
    _progress(f"committee DKG fixture: {n} dealers x t={COMMITTEE_DKG_T}")
    g = sch.key_group
    t = COMMITTEE_DKG_T
    dpolys = [tbls.PriPoly([rng.randrange(_R) for _ in range(t)])
              for _ in range(n)]
    dcommits = [[g.curve.mul(g.curve.gen, c) for c in p.coeffs]
                for p in dpolys]
    holder = 3
    dshares = [p.eval(holder).value for p in dpolys]
    before = dkg_device.dispatch_count()
    t0 = time.perf_counter()
    ok = dkg_device.verify_shares(g, dcommits, holder, dshares)
    old = dcommits[0]
    ctm = dkg_device.constant_terms_match(
        g, old, range(n), [tbls.PubPoly(g, old).eval(d) for d in range(n)])
    dkg_dt = time.perf_counter() - t0
    assert all(ok) and all(ctm)
    stats["committee_dkg_n"] = n
    stats["committee_dkg_t"] = t
    stats["committee_dkg_dispatches"] = \
        dkg_device.dispatch_count() - before
    stats["committee_dkg_wall_s"] = round(dkg_dt, 2)
    return rounds / dt


_RUNNERS = {
    1: "chained_catchup",
    2: "unchained_resident",
    3: "partials_recover",
    4: "mixed_4chains",
    5: "streamed_store",
    6: "coalesced_service",
    7: "multidevice_scaleout",
    8: "committee_scale",
    9: "multitenant_serving",
}
# Order: config 2 compiles/loads the shared G1@PAD program that 5, 6, 7,
# 9, 3 and 4 reuse; G2 (1, then 4) go after the G1 family so a G2
# compile overrun cannot starve the G1 numbers (9 sits between — its
# heavy-g2 tenant shares config 4's G2-unchained flavor); 8 last (its
# (1, n) partials program is unique to it).
_ORDER = [2, 5, 6, 7, 3, 1, 4, 9, 8]


def _child(indices):
    """Child: run the given configs IN ONE PROCESS (compiled programs are
    shared), printing one flushed JSON line per finished config."""
    _jax_setup()
    for idx in indices:
        stats = {}
        fns = {
            1: lambda: bench_chained_catchup(stats),
            2: bench_unchained_resident,
            3: bench_partials_recover,
            4: bench_mixed_4chains,
            5: lambda: bench_streamed_store(stats),
            6: lambda: bench_coalesced_service(stats),
            7: lambda: bench_multidevice_scaleout(stats),
            8: lambda: bench_committee_scale(stats),
            9: lambda: bench_multitenant_serving(stats),
        }
        t0 = time.monotonic()
        try:
            value = fns[idx]()
            stats[f"{_RUNNERS[idx]}_wall_s"] = round(time.monotonic() - t0, 1)
            # configs 1-5 drive BatchBeaconVerifier directly: success means
            # the device really served (a dead chip errors out, it cannot
            # silently produce numbers); config 6 self-reports via the
            # service's failover stats above
            stats.setdefault(f"{_RUNNERS[idx]}_backend", "device")
            print(json.dumps({"config": idx, "value": round(value, 1),
                              "stats": stats}), flush=True)
        except Exception as e:  # one failed config must not hide the others
            print(json.dumps({"config": idx, "value": None,
                              "error": f"{type(e).__name__}: {e}"[:200]}),
                  flush=True)


_LAST_EMIT = {"line": None}


def _emit(configs, stats):
    """Print the full cumulative result line (the driver parses the last).

    Consecutive DUPLICATE lines are suppressed: the r05 tail printed the
    identical cumulative record three times (the reader thread emits
    after the final config, then main() emitted again unconditionally) —
    the final record now lands exactly once unless something changed."""
    headline, headline_config = 0.0, None
    for name in ("streamed_store", "unchained_resident"):
        if configs.get(name):
            headline, headline_config = configs[name], name
            break
    else:
        for name, v in configs.items():
            if v:
                headline, headline_config = v, name
                break
    # which backend served each config (device | host_fallback), and ONE
    # top-level degraded flag: a run where anything fell back to the host
    # path, errored, or never reached the chip must be impossible to
    # misread as healthy device numbers (the r04 silent-zero postmortem)
    backends = {name: stats.get(f"{name}_backend") for name in configs
                if stats.get(f"{name}_backend")}
    degraded = (any(b != "device" for b in backends.values())
                or any(f"{name}_error" in stats for name in configs)
                or "device_error" in stats
                or headline == 0.0)
    out = {
        "metric": "beacon_verify_rounds_per_sec",
        "value": headline,
        "headline_config": headline_config,
        "unit": "rounds/s",
        "vs_baseline": round(headline / BASELINE_RPS, 3),
        "degraded": degraded,
        # serving-plane headline (tools/loadgen.py --selftest, CPU-only):
        # REST reads served/sec through the bounded edge + admission
        # controller, and the shed ratio under the deliberate overload
        "loadgen": {
            k.replace("loadgen_", ""): stats[k]
            for k in ("loadgen_rounds_served_per_s", "loadgen_shed_ratio",
                      "loadgen_shed_well_formed", "loadgen_error")
            if k in stats} or None,
        # the pad x depth occupancy sweep (tools/autotune.py; ISSUE 10)
        "tuning": {
            k.replace("tuning_", ""): stats[k]
            for k in ("tuning_platform", "tuning_winner", "tuning_sweep",
                      "tuning_file_entries", "tuning_error")
            if k in stats} or None,
        "backends": backends,
        "configs": configs,
        "n": {"streamed_store": N_STREAM, "unchained_resident": N_RESIDENT,
              "chained_catchup": N_CHAINED,
              "partials_recover": N_PARTIAL_ROUNDS,
              "mixed_4chains": N_CHAINED + 3 * N_MIXED,
              "coalesced_service": N_STREAM,
              "multidevice_scaleout": N_MD,
              "committee_scale": COMMITTEE_N,
              "multitenant_serving": N_TENANT * TENANT_PASSES,
              **stats},
    }
    line = json.dumps(out)
    if line != _LAST_EMIT["line"]:
        _LAST_EMIT["line"] = line
        print(line, flush=True)
    return headline


def _sweep_numbers(stats):
    """Record the pad x depth occupancy sweep (ISSUE 10): the autotune
    selftest — a tiny CPU-safe sweep that also proves the service
    consults its TUNING.json — runs on every bench round so the BENCH
    artifact carries the depth/width numbers next to the verify numbers.
    DRAND_TPU_BENCH_SWEEP=0 skips it (it costs a couple of tiny-pad
    compiles); failure is recorded, never fatal."""
    import subprocess
    if os.environ.get("DRAND_TPU_BENCH_SWEEP", "1") == "0":
        return
    at = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "autotune.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)          # cache-key hygiene (r3 postmortem)
    plat = os.environ.get("DRAND_TPU_BENCH_PLATFORM")
    if plat:
        env["JAX_PLATFORMS"] = plat
    try:
        proc = subprocess.run(
            [sys.executable, at, "--selftest"],
            capture_output=True, text=True, timeout=900, env=env)
        line = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
        rep = json.loads(line)
        stats["tuning_platform"] = rep.get("platform")
        stats["tuning_winner"] = rep.get("winner")
        stats["tuning_sweep"] = rep.get("sweep")
        if proc.returncode != 0 or not rep.get("ok"):
            stats["tuning_error"] = (
                f"selftest exit {proc.returncode}: consulted="
                f"{rep.get('consulted')}")
    except Exception as e:
        stats["tuning_error"] = f"{type(e).__name__}: {e}"[:200]
    # the round's committed TUNING.json (if any): what the service would
    # actually consult on this host — recorded so a chip round's sweep
    # results are part of its BENCH artifact
    from drand_tpu.crypto import tuning
    path = tuning.tuning_path()
    if path:
        stats["tuning_file_entries"] = tuning.load_entries(path)


def _loadgen_numbers(stats):
    """Record the serving-plane headline (ROADMAP 5a): a short in-process
    loadgen selftest — CPU-only, independent of the chip — whose
    rounds-served/sec + shed-ratio land next to the degraded flag.  Any
    failure is recorded, never fatal: the verify numbers must not hostage
    the edge numbers or vice versa."""
    import subprocess
    lg = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "loadgen.py")
    try:
        proc = subprocess.run(
            [sys.executable, lg, "--selftest", "--json", "--duration", "3"],
            capture_output=True, text=True, timeout=120)
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("{")][-1]
        rep = json.loads(line)
        stats["loadgen_rounds_served_per_s"] = rep["rounds_served_per_s"]
        stats["loadgen_shed_ratio"] = rep["shed_ratio"]
        stats["loadgen_shed_well_formed"] = rep["shed_well_formed"]
        if proc.returncode != 0:
            # numbers are recorded, but a failing selftest (errors, or a
            # flood that never shed) must be visible in the artifact
            stats["loadgen_error"] = (
                f"selftest exit {proc.returncode}: ok={rep.get('ok')} "
                f"shed={rep.get('shed')} errors={rep.get('errors')}")
    except Exception as e:
        stats["loadgen_error"] = f"{type(e).__name__}: {e}"[:200]


def main():
    import subprocess
    import threading

    which = _configs()
    order = [i for i in _ORDER if i in which]
    configs = {_RUNNERS[i]: None for i in order}
    stats = {}
    _loadgen_numbers(stats)
    _sweep_numbers(stats)
    # per-config ceiling (a hung compile blocks in native code and can
    # only be killed from outside) and a whole-bench budget
    cfg_budget = int(os.environ.get("DRAND_TPU_BENCH_CONFIG_TIMEOUT", "2400"))
    total_budget = int(os.environ.get("DRAND_TPU_BENCH_TOTAL_TIMEOUT", "5400"))
    deadline = time.monotonic() + total_budget

    # children must see a clean accelerator env: a driver-exported
    # XLA_FLAGS / JAX_PLATFORMS would change the compilation-cache key and
    # force a from-scratch compile of every program (r3 postmortem).
    # DRAND_TPU_BENCH_PLATFORM pins the child platform explicitly (local
    # CPU smoke tests of the bench protocol).
    env = dict(os.environ)
    for k in ("XLA_FLAGS", "JAX_PLATFORMS"):
        env.pop(k, None)
    plat = os.environ.get("DRAND_TPU_BENCH_PLATFORM")
    if plat:
        env["JAX_PLATFORMS"] = plat

    # One process per chip: the selftest subprocesses above have exited,
    # and this parent never imports jax, so the child below is the only
    # process that brings a backend up.
    assert "jax" not in sys.modules, "bench parent must not import jax"
    fatal = []

    remaining = list(order)
    attempt = 0
    while remaining and time.monotonic() < deadline - 30 and attempt < 4:
        attempt += 1
        print(f"# child {attempt}: configs {remaining}", file=sys.stderr,
              flush=True)
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--run",
             ",".join(map(str, remaining))],
            stdout=subprocess.PIPE, stderr=sys.stderr, text=True, env=env)

        done_here = []
        last_progress = time.monotonic()

        def _reader():
            nonlocal last_progress
            for line in proc.stdout:
                try:
                    res = json.loads(line)
                except ValueError:
                    continue
                if "fatal" in res:            # no chip: stop, no retry
                    fatal.append(res["fatal"])
                    continue
                if "progress" in res:         # intra-config heartbeat
                    last_progress = time.monotonic()
                    print(f"#   .. {res['progress']}", file=sys.stderr,
                          flush=True)
                    continue
                idx = res.get("config")
                name = _RUNNERS.get(idx)
                if name is None:
                    continue
                last_progress = time.monotonic()
                done_here.append(idx)
                if res.get("value"):
                    configs[name] = res["value"]
                elif res.get("error"):
                    stats[f"{name}_error"] = res["error"]
                stats.update(res.get("stats", {}))
                print(f"#   {name} -> {res.get('value')}", file=sys.stderr,
                      flush=True)
                _emit(configs, stats)

        th = threading.Thread(target=_reader, daemon=True)
        th.start()
        while proc.poll() is None:
            now = time.monotonic()
            if now > deadline or now - last_progress > cfg_budget:
                which_cfg = next((i for i in remaining
                                  if i not in done_here), None)
                if which_cfg is not None:
                    stats[f"{_RUNNERS[which_cfg]}_error"] = (
                        "timeout: killed after "
                        f"{now - last_progress:.0f}s without progress")
                proc.kill()
                break
            time.sleep(1.0)
        proc.wait(timeout=30)
        th.join(timeout=10)
        if fatal:
            stats["device_error"] = fatal[0]
            for idx in remaining:
                stats.setdefault(f"{_RUNNERS[idx]}_error", fatal[0])
            _emit(configs, stats)
            sys.exit(1)
        # drop finished configs; on timeout also drop the one that hung
        remaining = [i for i in remaining if i not in done_here]
        if remaining and proc.returncode != 0:
            hung = remaining[0]
            if f"{_RUNNERS[hung]}_error" not in stats:
                stats[f"{_RUNNERS[hung]}_error"] = (
                    f"child exit {proc.returncode}")
            remaining = remaining[1:]

    for idx in remaining:                 # never attempted: say why
        name = _RUNNERS[idx]
        if f"{name}_error" not in stats:
            stats[f"{name}_error"] = "skipped: total bench budget exhausted"
    headline = _emit(configs, stats)
    if headline == 0.0:
        sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--run":
        _child([int(x) for x in sys.argv[2].split(",")])
    else:
        main()
