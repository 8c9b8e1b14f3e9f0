"""Batched threshold-partial verification (drand_tpu/crypto/partials.py)
against the host tbls golden path.  Groups stay tiny (n=3), so each
orientation compiles one program per round count.
"""

import numpy as np
import pytest

from drand_tpu.crypto import partials, tbls
from drand_tpu.crypto.schemes import scheme_from_name


def _setup(scheme_id, t=2, n=3):
    sch = scheme_from_name(scheme_id)
    poly = tbls.PriPoly.random(t, secret=424243)
    shares = poly.shares(n)
    pp = poly.commit(sch.key_group)
    return sch, shares, pp, partials.BatchPartialVerifier(sch, pp, n)


@pytest.mark.parametrize("scheme_id", ["bls-unchained-on-g1",
                                       "pedersen-bls-unchained"])
def test_verify_partials_happy_and_fallback(scheme_id):
    sch, shares, pp, bv = _setup(scheme_id)
    msgs = [sch.digest_beacon(r, None) for r in (1, 2)]
    rows = [[tbls.sign_partial(sch, shares[i], m) for i in (0, 2)] for m in msgs]

    # happy path: RLC accepts everything the host accepts
    ok = bv.verify_partials(msgs, rows)
    assert ok.all()
    for m, row in zip(msgs, rows):
        for p in row:
            assert tbls.verify_partial(sch, pp, m, p)

    # corruption is localised by halves of the slot mask
    bad = bytearray(rows[1][0])
    bad[10] ^= 1
    rows2 = [rows[0], [bytes(bad), rows[1][1]]]
    assert bv.verify_partials(msgs, rows2).tolist() == [[True, True], [False, True]]
    assert not tbls.verify_partial(sch, pp, msgs[1], bytes(bad))

    # ragged rows pad with False; out-of-range signer index rejected
    forged = (5).to_bytes(2, "big") + rows[1][1][2:]
    rows3 = [[rows[0][0]], [forged, rows[1][1]]]
    assert bv.verify_partials(msgs, rows3).tolist() == [[True, False], [False, True]]

    # wrong-index partial (valid sig bytes under another share) fails
    swapped = rows[0][1][:2] + rows[0][0][2:]  # index 2 prefix, share-0 sig
    assert bv.verify_partials([msgs[0]], [[swapped]]).tolist() == [[False]]
    assert not tbls.verify_partial(sch, pp, msgs[0], swapped)


def test_verify_partials_empty():
    sch, shares, pp, bv = _setup("bls-unchained-on-g1")
    assert bv.verify_partials([], []).shape == (0, 0)
    assert bv.verify_partials([b"x"], [[]]).shape == (1, 0)


@pytest.mark.parametrize("scheme_id", ["bls-unchained-on-g1",
                                       "pedersen-bls-unchained"])
def test_verify_partials_non_decompressable_slot_localized(scheme_id):
    """ISSUE 10: the fast path decompresses ON DEVICE (the fused
    sqrt_ratio front end), so an x with no y on the curve is caught by
    the device parse_ok and dropped by that per-slot flag — matching
    the host golden decoder slot for slot."""
    sch, shares, pp, bv = _setup(scheme_id)
    msgs = [sch.digest_beacon(r, None) for r in (1, 2)]
    rows = [[tbls.sign_partial(sch, shares[i], m) for i in (0, 1)]
            for m in msgs]
    import drand_tpu.crypto.host.serialize as HS
    dec = HS.g2_from_bytes if sch.sig_group.point_len == 96 \
        else HS.g1_from_bytes
    found = False
    for tweak in range(1, 64):
        cand = bytearray(rows[0][1])
        cand[-1] ^= tweak                   # low x bits, index untouched
        try:
            dec(bytes(cand[2:]), check_subgroup=False)
        except (ValueError, AssertionError):
            found = True
            break
    assert found, "no non-decompressable tweak found"
    rows2 = [[rows[0][0], bytes(cand)], rows[1]]
    got = bv.verify_partials(msgs, rows2)
    assert got.tolist() == [[True, False], [True, True]]
    # host golden agrees the tweaked partial is invalid
    assert not tbls.verify_partial(sch, pp, msgs[0], bytes(cand))


@pytest.mark.parametrize("scheme_id", ["bls-unchained-on-g1",
                                       "pedersen-bls-chained"])
def test_one_program_for_any_slot_count_and_signer_subset(scheme_id):
    """A round's slots are padded to the group size and every node is a
    signer row: 1 slot, n slots and a subset of others (with one invalid
    partial, localised by halves) all run the first call's program."""
    from drand_tpu import metrics
    n = 3
    sch, shares, pp, bv = _setup(scheme_id, t=2, n=n)
    prev = bytes(96) if sch.chained else None
    msg = sch.digest_beacon(9, prev)
    parts = [tbls.sign_partial(sch, s, msg) for s in shares]
    wrong = tbls.sign_partial(sch, shares[1], sch.digest_beacon(8, prev))
    g = "g2" if sch.sig_group.point_len == 96 else "g1"
    flavour = f"batch.first_call/{g}_partials_rlc.fields@{n}"
    assert bv.verify_partials([msg], [parts[2:]]).tolist() == [[True]]
    assert bv.verify_partials([msg], [parts]).tolist() == [[True] * n]
    assert bv.verify_partials([msg], [[parts[2], wrong]]).tolist() \
        == [[True, False]]
    assert bv.verify_partials([msg], [[wrong]]).tolist() == [[False]]
    assert metrics.totals()[flavour][0] == 1
