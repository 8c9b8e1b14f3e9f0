"""Device batch pipelines: verify_batch / verify_chain / sign_batch /
recover_batch (drand_tpu/crypto/batch.py) — the framework's flagship ops.

Batch sizes stay at the minimum pad (8) so every test shares one compiled
shape per pipeline kind.
"""

import os

import numpy as np
import pytest

from drand_tpu.chain import Beacon
from drand_tpu.crypto import batch, tbls
from drand_tpu.crypto.schemes import list_schemes, scheme_from_name

from test_host_crypto import MAINNET_BEACONS


def _keyed_verifier(scheme_id, seed=b"batch-test"):
    sch = scheme_from_name(scheme_id)
    sec, pub = sch.keypair(seed=seed)
    return sch, sec, batch.BatchBeaconVerifier(sch, sch.public_bytes(pub))


def _signed_chain(sch, sec, n):
    """Host-signed beacons (chained linkage when the scheme is chained)."""
    prev = None
    beacons = []
    for r in range(1, n + 1):
        sig = sch.sign(sec, sch.digest_beacon(r, prev if sch.chained else None))
        beacons.append(Beacon(round=r, signature=sig,
                              previous_sig=prev if sch.chained else None))
        prev = sig
    return beacons


# ---------------------------------------------------------------------------
# verify_batch
# ---------------------------------------------------------------------------

def test_verify_batch_mainnet_vectors_g2():
    """Both chained mainnet beacons under their own pubkeys + a corrupted
    copy: RLC fails, the exact fallback localizes the bad round."""
    sch_id, round_, pub, sig, prev = MAINNET_BEACONS[0]
    ver = batch.BatchBeaconVerifier(scheme_from_name(sch_id), bytes.fromhex(pub))
    sig_b, prev_b = bytes.fromhex(sig), bytes.fromhex(prev)
    bad_sig = bytearray(sig_b)
    bad_sig[6] ^= 1

    got = ver.verify_batch([round_, round_ + 1, round_],
                           [sig_b, sig_b, bytes(bad_sig)],
                           [prev_b, prev_b, prev_b])
    assert got.tolist() == [True, False, False]


def test_verify_batch_mainnet_vector_g1():
    sch_id, round_, pub, sig, _ = MAINNET_BEACONS[3]
    ver = batch.BatchBeaconVerifier(scheme_from_name(sch_id), bytes.fromhex(pub))
    got = ver.verify_batch([round_, round_ + 1], [bytes.fromhex(sig)] * 2)
    assert got.tolist() == [True, False]


def test_verify_batch_all_valid_rlc_path():
    sch, sec, ver = _keyed_verifier("bls-unchained-on-g1")
    beacons = _signed_chain(sch, sec, 8)
    got = ver.verify_batch([b.round for b in beacons],
                           [b.signature for b in beacons])
    assert got.all()


def test_verify_batch_single_and_garbage():
    sch, sec, ver = _keyed_verifier("bls-unchained-on-g1")
    [b] = _signed_chain(sch, sec, 1)
    assert ver.verify_batch([b.round], [b.signature]).tolist() == [True]
    # malformed signature bytes never verify and never crash
    assert ver.verify_batch([1, 1], [b"\x00" * 48, b.signature]).tolist() == [False, True]
    assert ver.verify_batch([], []).tolist() == []


def test_verify_batch_localizes_corruption():
    sch, sec, ver = _keyed_verifier("bls-unchained-on-g1")
    beacons = _signed_chain(sch, sec, 6)
    sigs = [b.signature for b in beacons]
    sigs[3] = sigs[2]  # valid point, wrong round
    got = ver.verify_batch([b.round for b in beacons], sigs)
    assert got.tolist() == [True, True, True, False, True, True]


# ---------------------------------------------------------------------------
# verify_chain
# ---------------------------------------------------------------------------

def test_verify_chain_linkage():
    sch, sec, ver = _keyed_verifier("pedersen-bls-chained")
    beacons = _signed_chain(sch, sec, 5)
    ok, valid = ver.verify_chain(beacons)
    assert ok and valid.all()

    # break the linkage of round 4 (its own signature still verifies
    # against its stored previous_sig, but the link test must flag it)
    broken = list(beacons)
    broken[3] = Beacon(round=4, signature=beacons[3].signature,
                       previous_sig=beacons[1].signature)
    ok, valid = ver.verify_chain(broken)
    assert not ok
    assert not valid[3]


# ---------------------------------------------------------------------------
# sign_batch / recover_batch vs host golden
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme_id", list_schemes())
def test_recover_batch_matches_host(scheme_id):
    sch = scheme_from_name(scheme_id)
    t, n = 3, 5
    poly = tbls.PriPoly.random(t, secret=424242)
    shares = poly.shares(n)
    pub_poly = poly.commit(sch.key_group)

    rounds = [11, 12]
    idx_sets = [[0, 2, 4], [1, 2, 3]]
    indices, partials, expected = [], [], []
    for r, idxs in zip(rounds, idx_sets):
        msg = sch.digest_beacon(r, None)
        indices.append(idxs)
        partials.append([sch.sign(shares[i].value, msg) for i in idxs])
        host = tbls.recover(
            sch, pub_poly, msg,
            [tbls.sign_partial(sch, shares[i], msg) for i in idxs], t, n)
        expected.append(host)
        assert host == sch.sign(poly.secret(), msg)

    got = batch.recover_batch(sch, indices, partials)
    assert got == expected


def test_verify_stream_chunks_and_localizes():
    """verify_stream (BASELINE config 5 path): double-buffered chunked
    replay delivers per-chunk verdicts and still localizes a corruption."""
    from drand_tpu.crypto import batch, schemes
    from drand_tpu.chain.beacon import Beacon

    sch = schemes.scheme_from_name(schemes.SHORT_SIG_SCHEME_ID)
    sec, pub = sch.keypair(seed=b"stream-test")
    ver = batch.BatchBeaconVerifier(sch, sch.public_bytes(pub))
    n = 24
    msgs = [sch.digest_beacon(r, None) for r in range(1, n + 1)]
    sigs = batch.sign_batch(sch, sec, msgs)
    beacons = [Beacon(round=r, signature=s)
               for r, s in zip(range(1, n + 1), sigs)]
    beacons[13] = Beacon(round=14, signature=sigs[2])   # corrupt one round
    got_rounds, oks = [], []
    for rounds, ok in ver.verify_stream(iter(beacons), chunk_size=8):
        got_rounds.extend(rounds)
        oks.extend(ok.tolist())
    assert got_rounds == list(range(1, n + 1))
    assert oks[13] is False or oks[13] == False  # noqa: E712
    assert sum(1 for o in oks if not o) == 1


def test_verify_stream_depth_parity():
    """ISSUE 10 acceptance (CPU-scale): depth-k pipelined streams produce
    bit-identical verdicts to the depth-1 double buffer on the same
    inputs.  Pad/chunk stay at 8 so this reuses the file's compiled G1
    programs; DRAND_TPU_PARITY_PAD widens it for a warm-cache nightly
    (the property is pad-independent — one compiled program per pad,
    inert padding slots)."""
    sch, sec, ver = _keyed_verifier("bls-unchained-on-g1")
    pad = int(os.environ.get("DRAND_TPU_PARITY_PAD", "8"))
    n = 3 * pad
    msgs = [sch.digest_beacon(r, None) for r in range(1, n + 1)]
    sigs = batch.sign_batch(sch, sec, msgs)
    beacons = [Beacon(round=r, signature=s)
               for r, s in zip(range(1, n + 1), sigs)]
    beacons[pad + 1] = Beacon(round=pad + 2, signature=sigs[0])  # corrupt

    def run(depth):
        out = []
        for _, ok in ver.verify_stream(iter(beacons), chunk_size=pad,
                                       depth=depth):
            out.extend(ok.tolist())
        return out

    base = run(1)
    assert sum(1 for o in base if not o) == 1 and not base[pad + 1]
    for depth in (2, 3):
        assert run(depth) == base, f"depth {depth} diverged from depth 1"


def test_pad_width_parity():
    """Wider pads produce bit-identical verdicts: the same inputs through
    pad_to=8 and pad_to=16 verifiers (the CPU-scale analogue of the
    8192-vs-16384 sweep points; padding slots are inert by construction)."""
    sch, sec, _ = _keyed_verifier("bls-unchained-on-g1")
    beacons = _signed_chain(sch, sec, 12)
    sigs = [b.signature for b in beacons]
    sigs[7] = sigs[1]                       # valid point, wrong round
    rounds = [b.round for b in beacons]
    pub = sch.public_bytes(sch.keypair(seed=b"batch-test")[1])
    narrow = batch.BatchBeaconVerifier(sch, pub, pad_to=8)
    wide = batch.BatchBeaconVerifier(sch, pub, pad_to=16)
    got_n = narrow.verify_batch(rounds, sigs)
    got_w = wide.verify_batch(rounds, sigs)
    assert (got_n == got_w).all()
    assert not got_n[7] and got_n.sum() == 11


def test_recover_batch_is_one_dispatch():
    """ISSUE 10 acceptance: decompress + Lagrange recovery run as ONE
    device dispatch per batch, asserted on the module dispatch counter
    (CPU backend)."""
    sch = scheme_from_name("bls-unchained-on-g1")
    t, n = 3, 5
    poly = tbls.PriPoly.random(t, secret=77)
    shares = poly.shares(n)
    msg = sch.digest_beacon(5, None)
    partials = [[sch.sign(shares[i].value, msg) for i in (0, 1, 3)]]
    batch.recover_batch(sch, [[0, 1, 3]], partials)     # warm/compile
    before = batch.dispatch_count()
    out = batch.recover_batch(sch, [[0, 1, 3]], partials)
    assert batch.dispatch_count() - before == 1
    # and the recovered signature is the collective one
    pub_poly = poly.commit(sch.key_group)
    host = tbls.recover(sch, pub_poly, msg,
                        [tbls.sign_partial(sch, shares[i], msg)
                         for i in (0, 1, 3)], t, n)
    assert out == [host]


def test_dispatch_packed_retry():
    """Review regression (PR 9): the verify service's failover ladder
    re-invokes dispatch_packed ONCE after a transient fault — the retry
    must find the encoding intact (which would otherwise turn every
    transient fault into a premature host failover)."""
    sch, sec, ver8 = _keyed_verifier("bls-unchained-on-g1")
    pub = sch.public_bytes(sch.keypair(seed=b"batch-test")[1])
    ver = batch.BatchBeaconVerifier(sch, pub, pad_to=8)
    msgs = [sch.digest_beacon(r, None) for r in range(1, 4)]
    sigs = batch.sign_batch(sch, sec, msgs)
    packed = ver.pack_chunk([1, 2, 3], sigs)
    orig = ver._rlc_dispatch
    calls = {"n": 0}

    def flaky(enc, n, front=None):
        calls["n"] += 1
        assert enc is not None, "retry saw a consumed encoding"
        if calls["n"] == 1:
            raise ConnectionError("transient dispatch fault")
        return orig(enc, n, front=front)

    ver._rlc_dispatch = flaky
    with pytest.raises(ConnectionError):
        ver.dispatch_packed(packed)
    verdict = ver.dispatch_packed(packed)      # the ladder's one retry
    ok = ver.resolve_packed(packed, verdict)
    assert ok.tolist() == [True, True, True]
    assert calls["n"] == 2


def test_recover_batch_rejects_bad_encodings():
    """Host-detectable garbage raises before any device work; an x with
    no y on the curve raises via the fused pipeline's device parse_ok."""
    sch = scheme_from_name("bls-unchained-on-g1")
    t, n = 2, 3
    poly = tbls.PriPoly.random(t, secret=99)
    shares = poly.shares(n)
    msg = sch.digest_beacon(9, None)
    good = [sch.sign(shares[i].value, msg) for i in (0, 1)]
    # wrong length -> host wire parse
    with pytest.raises(ValueError):
        batch.recover_batch(sch, [[0, 1]], [[good[0], good[1][:-1]]])
    # flip low x bits until the host decoder rejects (no y on curve),
    # then the fused device path must reject the same bytes
    from drand_tpu.crypto.host import serialize as HS
    found = False
    for tweak in range(1, 64):
        cand = bytearray(good[1])
        cand[-1] ^= tweak
        try:
            HS.g1_from_bytes(bytes(cand), check_subgroup=False)
        except (ValueError, AssertionError):
            found = True
            with pytest.raises(ValueError):
                batch.recover_batch(sch, [[0, 1]],
                                    [[good[0], bytes(cand)]])
            break
    assert found, "no non-decompressable tweak found in 64 tries"


def test_verify_service_device_end_to_end():
    """The resident verify service over a REAL device backend (pad 8 —
    the same compiled G1-RLC program the rest of this file uses):
    coalesced submissions run through the pack/dispatch/resolve pipeline
    and fan back out with verdicts identical to a direct verify_batch."""
    from drand_tpu.crypto.verify_service import VerifyService

    sch, sec, ver = _keyed_verifier("bls-unchained-on-g1")
    beacons = _signed_chain(sch, sec, 12)
    beacons[5] = Beacon(round=6, signature=beacons[2].signature)
    rounds = [b.round for b in beacons]
    sigs = [b.signature for b in beacons]

    svc = VerifyService(pad=8, background_window=100.0)
    try:
        pub = sch.public_bytes(sch.keypair(seed=b"batch-test")[1])
        h = svc.handle(sch, pub, device=True)
        assert h.kind == "device"
        assert h.backend.pad_to == 8
        f1 = h.submit(rounds[:5], sigs[:5])
        f2 = h.submit(rounds[5:], sigs[5:])
        got = np.concatenate([f1.result(600), f2.result(600)])
        want = ver.verify_batch(rounds, sigs)
        assert (got == want).all()
        assert not got[5] and got.sum() == 11
        st = svc.stats()
        # 12 lanes at pad 8 = 2 coalesced dispatches for 2 submissions
        assert st["dispatches"] == 2 and st["submitted"] == 2
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# Device hash-to-field fronts (ISSUE 14): LoE-vector-pinned end-to-end
# parity on both groups, corruption included, and the one-dispatch
# property of the message-bytes-in entry points.
# ---------------------------------------------------------------------------

def test_device_h2f_mainnet_vector_g2_chained():
    """The chained LoE mainnet beacon through the RAW message front
    (prevSig/round words in, digest + xmd + h2f on device): verdicts
    bit-identical to the host-hashed oracle, corrupt copy rejected."""
    sch_id, round_, pub, sig, prev = MAINNET_BEACONS[0]
    ver = batch.BatchBeaconVerifier(scheme_from_name(sch_id),
                                    bytes.fromhex(pub), h2f_device=True)
    sig_b, prev_b = bytes.fromhex(sig), bytes.fromhex(prev)
    bad_sig = bytearray(sig_b)
    bad_sig[6] ^= 1
    packed = ver.pack_chunk([round_, round_ + 1, round_],
                            [sig_b, sig_b, bytes(bad_sig)],
                            [prev_b, prev_b, prev_b])
    assert packed[3] == batch.FRONT_RAW_CHAINED
    got = ver.verify_batch([round_, round_ + 1, round_],
                           [sig_b, sig_b, bytes(bad_sig)],
                           [prev_b, prev_b, prev_b])
    assert got.tolist() == [True, False, False]


def test_device_h2f_mainnet_vector_g1_unchained():
    sch_id, round_, pub, sig, _ = MAINNET_BEACONS[3]
    ver = batch.BatchBeaconVerifier(scheme_from_name(sch_id),
                                    bytes.fromhex(pub), h2f_device=True)
    got = ver.verify_batch([round_, round_ + 1], [bytes.fromhex(sig)] * 2)
    assert got.tolist() == [True, False]


def test_device_h2f_front_parity_with_host_oracle():
    """Freshly-signed G1 chain through BOTH fronts: identical verdicts,
    including a valid-point-wrong-round lane and a garbage lane."""
    sch, sec, _ = _keyed_verifier("bls-unchained-on-g1")
    beacons = _signed_chain(sch, sec, 8)
    sigs = [b.signature for b in beacons]
    sigs[3] = sigs[2]                      # valid point, wrong round
    sigs[6] = b"\x00" * 48                 # malformed wire bytes
    rounds = [b.round for b in beacons]
    pub = sch.public_bytes(sch.keypair(seed=b"batch-test")[1])
    dev = batch.BatchBeaconVerifier(sch, pub, h2f_device=True)
    host = batch.BatchBeaconVerifier(sch, pub, h2f_device=False)
    got_d = dev.verify_batch(rounds, sigs)
    got_h = host.verify_batch(rounds, sigs)
    assert (got_d == got_h).all()
    assert got_d.tolist() == [True, True, True, False,
                              True, True, False, True]


def test_device_h2f_stream_entry_is_one_dispatch():
    """One-dispatch acceptance for the message-bytes-in entry: a packed
    chunk through the raw front is exactly ONE dispatch (the fused front
    adds no stage), and the pack stage does zero host hashing while the
    pack-seconds accumulator advances."""
    from drand_tpu.ops import h2c as DHH

    sch, sec, _ = _keyed_verifier("bls-unchained-on-g1")
    beacons = _signed_chain(sch, sec, 6)
    rounds = [b.round for b in beacons]
    sigs = [b.signature for b in beacons]
    pub = sch.public_bytes(sch.keypair(seed=b"batch-test")[1])
    ver = batch.BatchBeaconVerifier(sch, pub, h2f_device=True)
    # warm the raw program so the counted pass measures steady
    # state (a cold pass takes the same count; this keeps timing honest)
    packed = ver.pack_chunk(rounds, sigs)
    assert ver.resolve_packed(packed, ver.dispatch_packed(packed)).all()
    hashed = DHH.host_h2f_count()
    before = batch.dispatch_count()
    packed = ver.pack_chunk(rounds, sigs)
    verdict = ver.dispatch_packed(packed)
    ok = ver.resolve_packed(packed, verdict)
    assert ok.all()
    assert batch.dispatch_count() - before == 1
    assert DHH.host_h2f_count() == hashed


def test_device_h2f_partials_digest_front_parity():
    """BatchPartialVerifier with the digest front (threshold forced to
    the test scale): identical accept/reject to the host-h2f oracle,
    including a corrupted slot."""
    import os as _os

    from drand_tpu.crypto.partials import BatchPartialVerifier

    sch = scheme_from_name("bls-unchained-on-g1")
    t, n_nodes, nr = 3, 5, 4
    poly = tbls.PriPoly.random(t, secret=0xFEED)
    shares = poly.shares(n_nodes)
    pub_poly = poly.commit(sch.key_group)
    msgs = [sch.digest_beacon(r, None) for r in range(1, nr + 1)]
    rows = [[i.to_bytes(2, "big") + sch.sign(shares[i].value, m)
             for i in (0, 1, 3)] for m in msgs]
    rows[2][1] = rows[1][1]                # valid partial, wrong round
    bpv = BatchPartialVerifier(sch, pub_poly, n_nodes)
    old = _os.environ.get("DRAND_H2F_DEVICE_MIN_N")
    try:
        _os.environ["DRAND_H2F_DEVICE_MIN_N"] = str(10 ** 9)
        want = bpv.verify_partials(msgs, rows)          # host front
        _os.environ["DRAND_H2F_DEVICE_MIN_N"] = "2"
        got = bpv.verify_partials(msgs, rows)           # digest front
    finally:
        if old is None:
            _os.environ.pop("DRAND_H2F_DEVICE_MIN_N", None)
        else:
            _os.environ["DRAND_H2F_DEVICE_MIN_N"] = old
    assert (got == want).all()
    assert not got[2][1] and got.sum() == 3 * nr - 1
