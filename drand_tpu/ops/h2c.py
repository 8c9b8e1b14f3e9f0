"""Device-side RFC 9380 hash-to-curve for G1 and G2 (batched, branchless).

Hybrid split per SURVEY.md §7 hard-part 3: the SHA-256 `expand_message_xmd`
runs on host (hashlib is native code, microseconds per message), producing
field elements u0, u1 per message; everything algebraic — the simplified SWU
map, the isogeny to E1/E2, point addition, cofactor clearing — runs on device
over the whole batch.

Design notes:
* All control flow is mask/select; square-detection and square roots are
  fixed-exponent pow scans (p = 3 mod 4 for Fp; norm-trick for Fp2, mirrored
  from the host golden `fp2_sqrt` and tested against it).
* The isogeny evaluation emits Jacobian coordinates directly
  (X = xn·xd·yd², Y = y·yn·xd³·yd², Z = xd·yd) — no field inversion anywhere
  in the map.
* Q0 and Q1 are mapped through the isogeny separately and added on the
  *target* curve (the isogeny is a group hom), so the a=0 complete addition
  of ops/curve.py applies; E'-side addition would need a≠0 doubling formulas.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np

from . import limbs as L
from . import tower as T
from . import curve as DC
from ..crypto.host.params import (
    P, HTF_L, ISO_A1, ISO_B1, ISO_A2, ISO_B2, Z1, Z2, DST_G1, DST_G2,
)
from ..crypto.host.h2c import (
    hash_to_field_fp, hash_to_field_fp2,
    _K1, _K2, _K3, _K4,
)
from ..crypto.host._iso_g1 import XNUM as G1XN, XDEN as G1XD, YNUM as G1YN, YDEN as G1YD

# ---------------------------------------------------------------------------
# Constants (encoded once)
# ---------------------------------------------------------------------------

_A1 = L.encode_mont(ISO_A1)
_B1 = L.encode_mont(ISO_B1)
_Z1 = L.encode_mont(Z1)
_A2 = T.encode_fp2(ISO_A2)
_B2 = T.encode_fp2(ISO_B2)
_Z2 = T.encode_fp2(Z2)

from ..crypto.host import field as HF

_SQRT_EXP = (P + 1) // 4
_QR_EXP = (P - 1) // 2

_G1_ISO = tuple(tuple(L.encode_mont(c) for c in cs) for cs in (G1XN, G1XD, G1YN, G1YD))
_G2_ISO = tuple(tuple(T.encode_fp2(c) for c in cs) for cs in (_K1, _K2, _K3, _K4))


# ---------------------------------------------------------------------------
# Fp helpers
# ---------------------------------------------------------------------------

def fp_is_square(a):
    """Legendre via fixed pow; 0 counts as square."""
    ls = L.pow_fixed(a, _QR_EXP)
    return L.is_zero(a) | L.eq(ls, jnp.broadcast_to(L.ONE_M, ls.shape))


def fp_sqrt(a):
    """sqrt for squares (p = 3 mod 4); garbage for non-squares (caller selects)."""
    return L.pow_fixed(a, _SQRT_EXP)


def fp_sgn0(a):
    """Parity of the canonical representative (Montgomery in)."""
    return L.from_mont(a)[..., 0] & 1


def fp2_sgn0(a):
    c0 = L.from_mont(a[0])
    c1 = L.from_mont(a[1])
    s0 = c0[..., 0] & 1
    z0 = jnp.all(c0 == 0, axis=-1).astype(L.U32)
    s1 = c1[..., 0] & 1
    return s0 | (z0 & s1)


_HALF_M = L.encode_mont((P + 1) // 2)


# ---------------------------------------------------------------------------
# Simplified SWU for G1 — RFC 9380 F.2.1.2 straight-line version (q = 3 mod 4)
#
# One (p-3)/4 pow replaces the generic path's field inversion (1/tv2) AND the
# dual-candidate sqrt: sqrt_ratio(gx1, gxd) yields both the square test and
# the root from a single chain.  The map emits x PROJECTIVELY (xn/xd) and the
# isogeny is evaluated on homogenized polynomials, so the whole
# hash-to-curve pipeline contains no inversion at all.
#
# The pow input is exposed via pre/post halves so callers can stack this
# chain with other (p-3)/4 chains (signature decompression) into ONE scan —
# pow scans cost the same per step at any lane width.
# ---------------------------------------------------------------------------

_C1_EXP = (P - 3) // 4
_c2_int = pow((-(Z1 ** 3)) % P, (P + 1) // 4, P)
assert _c2_int * _c2_int % P == (-(Z1 ** 3)) % P, "c2 = sqrt(-Z^3) must exist"
_C2_G1 = L.encode_mont(_c2_int)
_NA1 = L.encode_mont(P - ISO_A1)
_ZA_G1 = L.encode_mont(Z1 * ISO_A1 % P)


def _sswu_g1_pre(u):
    """Front half: everything up to the sqrt_ratio pow input tv4 = gx1·gxd³."""
    bc = lambda c: jnp.broadcast_to(c, u.shape)
    tv1 = L.mont_sqr(u)                               # u²
    tv3 = L.mont_mul(bc(_Z1), tv1)                    # Z·u²
    xd = L.add_mod(L.mont_sqr(tv3), tv3)              # Z²u⁴ + Zu²
    x1n = L.mont_mul(L.add_mod(xd, bc(L.ONE_M)), bc(_B1))
    xd = L.mont_mul(bc(_NA1), xd)                     # -A·(Z²u⁴+Zu²)
    xd = L.select(L.is_zero(xd), bc(_ZA_G1), xd)      # exceptional case
    xd2 = L.mont_sqr(xd)
    gxd, axd2, gx1a = L.mul_many(
        [(xd2, xd), (bc(_A1), xd2), (x1n, x1n)])      # xd³, A·xd², x1n²
    gx1 = L.mont_mul(L.add_mod(gx1a, axd2), x1n)      # x1n³ + A·x1n·xd²
    gx1 = L.add_mod(gx1, L.mont_mul(bc(_B1), gxd))    # … + B·xd³
    tv4a, tv2e = L.mul_many([(gxd, gxd), (gx1, gxd)])  # gxd², gx1·gxd
    tv4 = L.mont_mul(tv4a, tv2e)                      # gx1·gxd³
    return tv4, (u, tv1, tv3, x1n, xd, gxd, gx1, tv2e)


def _sswu_g1_post(e, ctx):
    """Back half: e = tv4^((p-3)/4) -> projective (xn, xd, y_affine)."""
    u, tv1, tv3, x1n, xd, gxd, gx1, tv2e = ctx
    bc = lambda c: jnp.broadcast_to(c, u.shape)
    y1, x2n, tv1u = L.mul_many(
        [(e, tv2e), (tv3, x1n), (tv1, u)])            # cand. sqrt(gx1/gxd)
    y2, ysq = L.mul_many([(L.mont_mul(y1, bc(_C2_G1)), tv1u), (y1, y1)])
    e2 = L.eq(L.mont_mul(ysq, gxd), gx1)              # gx1/gxd was square?
    xn = L.select(e2, x1n, x2n)
    y = L.select(e2, y1, y2)
    flip = fp_sgn0(u) != fp_sgn0(y)
    y = L.select(flip, L.neg_mod(y), y)
    return xn, xd, y


def _iso_g1_proj(xn, xd, y):
    """11-isogeny on projective x = xn/xd, affine y — homogenized Horner,
    Jacobian output, zero inversions (the generated coefficients are the
    same _iso_g1 constants the affine path uses)."""
    kxn, kxd, kyn, kyd = _G1_ISO                      # const-term-first
    bshape = xn.shape
    bc = lambda c: jnp.broadcast_to(c, bshape)
    # powers of xd up to max degree 15
    maxd = max(len(kxn), len(kxd), len(kyn), len(kyd)) - 1
    xdp = [None, xd]
    for i in range(2, maxd + 1):
        xdp.append(L.mont_mul(xdp[i // 2], xdp[i - i // 2]) if i > 2
                   else L.mont_sqr(xd))
    polys = [list(kxn), list(kxd), list(kyn), list(kyd)]
    degs = [len(p) - 1 for p in polys]
    accs = [bc(p[-1]) for p in polys]
    for r in range(max(degs)):
        pairs, meta = [], []
        for j, p in enumerate(polys):
            i = degs[j] - 1 - r                       # next coeff index
            if i < 0:
                continue
            pairs.append((accs[j], xn))
            pairs.append((bc(p[i]), xdp[degs[j] - i]))
            meta.append(j)
        prods = L.mul_many(pairs)
        for k, j in enumerate(meta):
            accs[j] = L.add_mod(prods[2 * k], prods[2 * k + 1])
    xn_h, xd_h, yn_h, yd_h = accs
    d1, yd2 = L.mul_many([(xd, xd_h), (yd_h, yd_h)])  # full x-denominator
    z, d12, yyn = L.mul_many([(d1, yd_h), (d1, d1), (y, yn_h)])
    X, d13 = L.mul_many([(xn_h, L.mont_mul(d1, yd2)), (d12, d1)])
    Y = L.mont_mul(yyn, L.mont_mul(d13, yd2))
    return (X, Y, z)


# ---------------------------------------------------------------------------
# Simplified SWU for G2 — straight-line sqrt_ratio for q = p^2 = 9 mod 16.
#
# Mirrors the r3 G1 treatment (VERDICT r3 #3): x stays projective (xn/xd),
# and ONE Fp2 pow scan with exponent E2 = (p^2-9)/16 replaces the generic
# path's field inversion (1/tv2), Legendre test and dual-candidate sqrt.
# Candidate selection after the scan (Wahby-Boneh "fast hashing to
# BLS12-381" sqrtdiv structure, constants derived in-module from the host
# golden field code):
#
#   w   = U·V^7,  e = w^E2,  gamma = e·U·V^3     =>  gamma^2 = (U/V)·zeta,
#   zeta = (U·V^7)^((q-1)/8) an 8th root of unity.
#   U/V square      : y in gamma·{1, s1, s2, s3}   (squares cover mu_4)
#   U/V non-square  : sqrt(Z^3·U/V) in gamma·{eta_j}, eta_j^2 = Z^3/zeta_j
#                     over the four primitive 8th roots zeta_j; then
#                     y = u^3 · that  (g(x2) = Z^3 u^6 g(x1)).
#
# Signature decompression rides the same exponent: sqrt(w) candidates are
# (e·w)·{1, s1, s2, s3} — so decompression (width N) and both SSWU maps
# (width 2N) share ONE scan at width 3N (pow scans cost per step, not per
# lane).
# ---------------------------------------------------------------------------

_E2_EXP = (P * P - 9) // 16
assert (P * P) % 16 == 9

# constants over the host golden field code (Fp2 = Fp[u]/(u^2+1))
_s1_h = (0, 1)                                     # sqrt(-1) = u
_s2_h = HF.fp2_sqrt(_s1_h)
_s3_h = HF.fp2_sqrt(HF.fp2_neg(_s1_h))
assert _s2_h is not None and _s3_h is not None
_Z2_cube = HF.fp2_mul(HF.fp2_sqr(Z2), Z2)
_roots8_h = [_s2_h, HF.fp2_mul(_s1_h, _s2_h), HF.fp2_neg(_s2_h),
             HF.fp2_neg(HF.fp2_mul(_s1_h, _s2_h))]  # primitive 8th roots
_etas_h = []
for _z8 in _roots8_h:
    _eta = HF.fp2_sqrt(HF.fp2_mul(_Z2_cube, HF.fp2_inv(_z8)))
    assert _eta is not None
    _etas_h.append(_eta)
_SQR_MULTS_G2 = tuple(T.encode_fp2(c) for c in ((1, 0), _s1_h, _s2_h, _s3_h))
_ETAS_G2 = tuple(T.encode_fp2(c) for c in _etas_h)
_NA2 = T.encode_fp2(HF.fp2_neg(ISO_A2))
_ZA_G2 = T.encode_fp2(HF.fp2_mul(Z2, ISO_A2))
_Z3_G2 = T.encode_fp2(_Z2_cube)


def _sswu_g2_pre(u):
    """Front half: everything up to the sqrt_ratio scan input w = U·V^7.

    U/V = g(x1) with x1 = x1n/xd projective (zero inversions)."""
    shape = u[0].shape
    bc2 = lambda c: jax.tree.map(lambda t: jnp.broadcast_to(t, shape), c)
    A, B, Z = bc2(_A2), bc2(_B2), bc2(_Z2)
    tv1 = T.fp2_sqr(u)                                # u²
    tv3 = T.fp2_mul(Z, tv1)                           # Z·u²
    xd = T.fp2_add(T.fp2_sqr(tv3), tv3)               # Z²u⁴ + Zu²
    one = T.fp2_ones(shape[:-1])
    x1n = T.fp2_mul(T.fp2_add(xd, one), B)
    xd = T.fp2_mul(bc2(_NA2), xd)                     # -A·(Z²u⁴+Zu²)
    xd = T.fp2_select(T.fp2_is_zero(xd), bc2(_ZA_G2), xd)
    xd2 = T.fp2_sqr(xd)
    xd3 = T.fp2_mul(xd2, xd)
    gx1 = T.fp2_mul(T.fp2_add(T.fp2_sqr(x1n), T.fp2_mul(A, xd2)), x1n)
    U = T.fp2_add(gx1, T.fp2_mul(B, xd3))             # x1n³ + A·x1n·xd² + B·xd³
    V = xd3
    V2 = T.fp2_sqr(V)
    UV3 = T.fp2_mul(U, T.fp2_mul(V2, V))              # U·V³ (gamma factor)
    w = T.fp2_mul(UV3, T.fp2_sqr(V2))                 # U·V⁷
    return w, (u, tv1, tv3, x1n, xd, U, V, UV3)


def _sswu_g2_post(e, ctx):
    """Back half: e = w^E2 -> projective (xn, xd, y_affine)."""
    u, tv1, tv3, x1n, xd, U, V, UV3 = ctx
    shape = u[0].shape
    bc2 = lambda c: jax.tree.map(lambda t: jnp.broadcast_to(t, shape), c)
    gamma = T.fp2_mul(e, UV3)                         # candidate sqrt(U/V)
    # QR candidates: gamma·{1, s1, s2, s3}
    cands = [gamma] + [T.fp2_mul(gamma, bc2(m)) for m in _SQR_MULTS_G2[1:]]
    y_qr, is_qr = None, None
    for c in cands:
        hit = T.fp2_eq(T.fp2_mul(T.fp2_sqr(c), V), U)
        y_qr = c if y_qr is None else T.fp2_select(hit, c, y_qr)
        is_qr = hit if is_qr is None else (is_qr | hit)
    # non-QR: sqrt(Z³·U/V) = gamma·eta_j; then y = u³·(that)
    z3u = T.fp2_mul(bc2(_Z3_G2), U)
    y_im = None
    for eta in _ETAS_G2:
        c = T.fp2_mul(gamma, bc2(eta))
        hit = T.fp2_eq(T.fp2_mul(T.fp2_sqr(c), V), z3u)
        y_im = c if y_im is None else T.fp2_select(hit, c, y_im)
    u3 = T.fp2_mul(T.fp2_mul(tv1, u), y_im)           # u³·sqrt(Z³U/V)
    xn = T.fp2_select(is_qr, x1n, T.fp2_mul(tv3, x1n))
    y = T.fp2_select(is_qr, y_qr, u3)
    flip = fp2_sgn0(u) != fp2_sgn0(y)
    y = T.fp2_select(flip, T.fp2_neg(y), y)
    return xn, xd, y


def _iso_g2_proj(xn, xd, y):
    """3-isogeny E2' -> E2 on projective x = xn/xd, affine y — homogenized
    Horner, Jacobian output, zero inversions (host constants _K1.._K4;
    degrees: xnum 3, xden 2, ynum 3, yden 3)."""
    kxn, kxd, kyn, kyd = _G2_ISO
    shape = xn[0].shape
    bc2 = lambda c: jax.tree.map(lambda t: jnp.broadcast_to(t, shape), c)
    xd2 = T.fp2_sqr(xd)
    xd3 = T.fp2_mul(xd2, xd)
    xdp = [None, xd, xd2, xd3]

    def homog(coeffs):                 # sum k_i · xn^i · xd^(deg-i)
        deg = len(coeffs) - 1
        acc = bc2(coeffs[deg])
        for i in range(deg - 1, -1, -1):
            acc = T.fp2_add(T.fp2_mul(acc, xn),
                            T.fp2_mul(bc2(coeffs[i]), xdp[deg - i]))
        return acc

    xn_h = homog(kxn)                  # deg 3
    xd_h = T.fp2_mul(homog(kxd), xd)   # deg 2, lifted to common deg 3
    yn_h = homog(kyn)                  # deg 3
    yd_h = homog(kyd)                  # deg 3
    z = T.fp2_mul(xd_h, yd_h)
    yd2 = T.fp2_sqr(yd_h)
    X = T.fp2_mul(T.fp2_mul(xn_h, xd_h), yd2)            # xn·xd·yd²
    xdh2 = T.fp2_sqr(xd_h)
    Y = T.fp2_mul(T.fp2_mul(y, yn_h),
                  T.fp2_mul(T.fp2_mul(xdh2, xd_h), yd2))  # y·yn·xd³·yd²
    return (X, Y, z)


# ---------------------------------------------------------------------------
# Isogeny evaluation -> Jacobian on the target curve (no inversions)
# ---------------------------------------------------------------------------

def _leaf_shape(x):
    while isinstance(x, tuple):
        x = x[0]
    return x.shape


def map_to_g1_jac(u):
    """SSWU + 11-isogeny: field element batch -> Jacobian points on E1."""
    tv4, ctx = _sswu_g1_pre(u)
    e = L.pow_fixed(tv4, _C1_EXP)
    return _iso_g1_proj(*_sswu_g1_post(e, ctx))


def map_to_g2_jac(u):
    """SSWU + 3-isogeny: Fp2 element batch -> Jacobian points on E2."""
    w, ctx = _sswu_g2_pre(u)
    e = T.fp2_pow_fixed(w, _E2_EXP)
    return _iso_g2_proj(*_sswu_g2_post(e, ctx))


# ---------------------------------------------------------------------------
# Full hash_to_curve pipelines (host hashing -> device algebra)
#
# The host loop below is the PARITY ORACLE and the below-threshold
# fallback (ISSUE 14): the device hash-to-field stages further down move
# the whole expand_message_xmd chain on-chip for the steady-state pack
# path, and every host-hashed message increments `_HOST_H2F` so tests
# (and bench) can pin "no O(n) host hashing above the threshold" to a
# counter instead of a timing.
# ---------------------------------------------------------------------------

# Locked: host-front handles on a multi-group
# service hash from one packer thread per group, and += is not atomic.
_HOST_H2F = {"n": 0}
_HOST_H2F_LOCK = threading.Lock()


def host_h2f_count() -> int:
    """Messages hash-to-field-expanded on the HOST (hashlib loop or the
    native C batch call) since process start — the observability hook
    for the device-h2f selection tests."""
    return _HOST_H2F["n"]


def hash_msgs_to_field_g1(msgs, dst=DST_G1):
    """Host: messages -> (u0_batch, u1_batch) Montgomery limb tensors.

    Equal-length batches go through the native C batch path (one call,
    threaded, limbs emitted directly in the device layout)."""
    from ..crypto.host import native
    with _HOST_H2F_LOCK:
        _HOST_H2F["n"] += len(msgs)
    if native.available() and msgs and all(len(m) == len(msgs[0]) for m in msgs):
        h = native.h2f_fp_limbs_batch([bytes(m) for m in msgs], dst)
        return jnp.asarray(h[:, 0]), jnp.asarray(h[:, 1])
    u0s, u1s = [], []
    for m in msgs:
        # oracle/below-threshold fallback; hot path = hash_to_field_fp_dev
        # tpu-vet: disable=trace
        u0, u1 = hash_to_field_fp(m, dst, 2)
        u0s.append(u0)
        u1s.append(u1)
    return L.encode_mont(u0s), L.encode_mont(u1s)


def hash_msgs_to_field_g2(msgs, dst=DST_G2):
    from ..crypto.host import native
    with _HOST_H2F_LOCK:
        _HOST_H2F["n"] += len(msgs)
    if native.available() and msgs and all(len(m) == len(msgs[0]) for m in msgs):
        h = native.h2f_fp2_limbs_batch([bytes(m) for m in msgs], dst)
        return ((jnp.asarray(h[:, 0]), jnp.asarray(h[:, 1])),
                (jnp.asarray(h[:, 2]), jnp.asarray(h[:, 3])))
    c = [[], [], [], []]
    for m in msgs:
        # parity oracle / fallback, see hash_msgs_to_field_g1
        # tpu-vet: disable=trace
        (a0, a1), (b0, b1) = hash_to_field_fp2(m, dst, 2)
        for lst, v in zip(c, (a0, a1, b0, b1)):
            lst.append(v)
    return ((L.encode_mont(c[0]), L.encode_mont(c[1])),
            (L.encode_mont(c[2]), L.encode_mont(c[3])))


# ---------------------------------------------------------------------------
# Device-resident hash-to-field (ISSUE 14): RFC 9380 expand_message_xmd
# + hash_to_field as batched device stages on top of ops/sha256.py, so a
# verify chunk's front becomes message-bytes-in -> curve-points-out in
# ONE dispatch.  All framing (Z_pad, l_i_b, DST', padding) is static at
# trace time; the per-lane data is the message words alone.
# ---------------------------------------------------------------------------

from . import sha256 as SHA  # noqa: E402  (after the host oracle above)


def expand_msg_xmd_dev(msg_words, msg_len: int, dst: bytes,
                       len_in_bytes: int):
    """Device expand_message_xmd: (..., k) uint32 BE message words of
    `msg_len` bytes per lane (partial final word high-packed) -> (...,
    len_in_bytes/4) uniform words.  dst / lengths are static.

    b_0 starts from the Z_pad midstate (64 static bytes = zero device
    blocks); b_1..b_ell are the sequential 2-block chain of the RFC —
    ell * 2 + ceil((msg_len + 47) / 64) compressions per lane total."""
    ell = (len_in_bytes + 31) // 32
    assert 0 < ell <= 255 and len(dst) <= 255 and len_in_bytes % 4 == 0
    dst_prime = dst + bytes([len(dst)])
    l_i_b = len_in_bytes.to_bytes(2, "big")
    b0 = SHA.sha256_words(msg_words, msg_len,
                          tail=l_i_b + b"\x00" + dst_prime,
                          prefix=b"\x00" * 64)
    bi = SHA.sha256_words(b0, tail=b"\x01" + dst_prime)
    out = [bi]
    for i in range(2, ell + 1):
        bi = SHA.sha256_words(b0 ^ bi, tail=bytes([i]) + dst_prime)
        out.append(bi)
    return jnp.concatenate(out, axis=-1)[..., :len_in_bytes // 4]


def hash_to_field_fp_dev(msg_words, msg_len: int, dst: bytes):
    """Device hash_to_field (count=2, L=64) for Fp: message words ->
    (u0, u1) canonical Montgomery limb tensors, bit-identical to the
    host `hash_to_field_fp` (OS2IP of each 64-byte chunk mod p)."""
    ub = expand_msg_xmd_dev(msg_words, msg_len, dst, 2 * HTF_L)
    return (L.be_words_to_mont(ub[..., :16]),
            L.be_words_to_mont(ub[..., 16:32]))


def hash_to_field_fp2_dev(msg_words, msg_len: int, dst: bytes):
    """Fp2 mirror: -> ((u0c0, u0c1), (u1c0, u1c1)) Montgomery limbs."""
    ub = expand_msg_xmd_dev(msg_words, msg_len, dst, 4 * HTF_L)
    chunk = lambda i: L.be_words_to_mont(ub[..., 16 * i:16 * (i + 1)])
    return ((chunk(0), chunk(1)), (chunk(2), chunk(3)))


def beacon_digests_dev(msg):
    """Device digest_beacon over a packed raw-message pytree (the pack
    path's wire formats; crypto/batch.py builds them with pure numpy):

      (round_words,)                      unchained: H(round8)
      (prev_words, round_words, has_prev) chained:   H(prevSig || round8),
                                          falling back to H(round8) where
                                          has_prev == 0 (the genesis slot
                                          whose previous_sig is absent —
                                          both block counts are static, so
                                          the select stays branchless)

    -> (..., 8) digest words, bit-identical to Scheme.digest_beacon."""
    if len(msg) == 1:
        return SHA.sha256_words(msg[0])
    prev_words, round_words, has_prev = msg
    d_chain = SHA.sha256_words(
        jnp.concatenate([jnp.asarray(prev_words), jnp.asarray(round_words)],
                        axis=-1))
    d_bare = SHA.sha256_words(round_words)
    return jnp.where((has_prev != 0)[..., None], d_chain, d_bare)


def hash_to_g2_jac(u0, u1):
    """Device: two field-element batches -> G2 Jacobian point batch (in-group).

    The two SSWU maps run as ONE stacked pass: the pow scans inside are
    latency-bound, so doubling their width is free while running the map
    twice doubles wall time."""
    u = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), u0, u1)
    q = map_to_g2_jac(u)
    n = _leaf_shape(u0)[0]
    q0 = jax.tree.map(lambda t: t[:n], q)
    q1 = jax.tree.map(lambda t: t[n:], q)
    r = DC.G2_DEV.add(q0, q1)
    return DC.g2_clear_cofactor(r)


def hash_to_g1_jac(u0, u1):
    u = jnp.concatenate([u0, u1], 0)
    q = map_to_g1_jac(u)
    n = u0.shape[0]
    q0 = jax.tree.map(lambda t: t[:n], q)
    q1 = jax.tree.map(lambda t: t[n:], q)
    r = DC.G1_DEV.add(q0, q1)
    return DC.g1_clear_cofactor(r)


# ---------------------------------------------------------------------------
# Device-side signature decompression: wire x-coordinate + sign flag -> point.
#
# The reference decompresses on CPU (one sqrt each, kilic asm); here the host
# only splits bytes into limb arrays (pure numpy, see crypto/batch.py) and
# the batched sqrt chain runs on device — this single-host-core environment
# makes per-point host work the bottleneck otherwise.
# ---------------------------------------------------------------------------

_HALF1_DEV = jnp.asarray(np.asarray(L.int_to_limbs((P + 1) // 2)))


def _g1_y2(x_can):
    """Decompression front half: wire x -> (x_mont, y² = x³ + 4)."""
    xm = L.to_mont(x_can)
    b = jnp.broadcast_to(DC.G1_DEV.b, xm.shape)
    return xm, L.add_mod(L.mont_mul(L.mont_sqr(xm), xm), b)


def _g1_recover_post(xm, y2, e, sign_bit):
    """Back half: e = y2^((p-3)/4) -> (Jacobian point, ok).

    y = e·y2 = y2^((p+1)/4) — the sqrt when y2 is a residue; sharing the
    (p-3)/4 exponent lets decompression ride the SSWU sqrt_ratio scan."""
    y = L.mont_mul(e, y2)
    ok = L.eq(L.mont_sqr(y), y2)
    larger = _fp_ge_half1(y)
    flip = larger ^ (sign_bit == 1)
    y = L.select(flip, L.neg_mod(y), y)
    one = jnp.broadcast_to(L.ONE_M, xm.shape)
    return (xm, y, one), ok


def g1_recover_y(x_can, sign_bit):
    """x (canonical limbs, batch), sign flag (0/1) -> (Jacobian point, ok).

    ok is False where x**3 + 4 is a non-residue (not on curve); y parity
    follows the zcash larger-half convention (host serialize.py:18-19)."""
    xm, y2 = _g1_y2(x_can)
    e = L.pow_fixed(y2, _C1_EXP)
    return _g1_recover_post(xm, y2, e, sign_bit)


def g1_decompress_and_hash(sig_x_can, sign_bit, u0, u1):
    """Fused G1 front end: signature decompression + hash_to_curve(u0, u1)
    with ONE (p-3)/4 pow scan across all three chains (width 3N) — pow
    scans cost per *step*, not per lane, so stacking is the free lunch.

    Returns (sig_jac, parse_ok, hm_jac) for the verification equation
    e(S, -g2)·e(H(m), pk) == 1 (crypto/schemes.go:166-204 scheme family)."""
    u = jnp.concatenate([u0, u1], 0)
    tv4, ctx = _sswu_g1_pre(u)
    xm, y2 = _g1_y2(sig_x_can)
    e = L.pow_fixed(jnp.concatenate([tv4, y2], 0), _C1_EXP)
    n2 = u.shape[0]
    q = _iso_g1_proj(*_sswu_g1_post(e[:n2], ctx))
    sig_jac, ok = _g1_recover_post(xm, y2, e[n2:], sign_bit)
    n = u0.shape[0]
    q0 = jax.tree.map(lambda t: t[:n], q)
    q1 = jax.tree.map(lambda t: t[n:], q)
    hm = DC.g1_clear_cofactor(DC.G1_DEV.add(q0, q1))
    return sig_jac, ok, hm


def _g2_y2(x0_can, x1_can):
    """Decompression front half: wire x -> (x_mont, y² = x³ + b)."""
    xm = (L.to_mont(x0_can), L.to_mont(x1_can))
    b = jax.tree.map(lambda c: jnp.broadcast_to(c, xm[0].shape), DC.G2_DEV.b)
    return xm, T.fp2_add(T.fp2_mul(T.fp2_sqr(xm), xm), b)


def _g2_recover_post(xm, y2, e, sign_bit):
    """Back half: e = y2^E2 -> (Jacobian point, ok).

    gamma = e·y2 = y2^((q+7)/16); the sqrt is gamma·{1,s1,s2,s3} when y2
    is a residue — sharing the E2 exponent lets decompression ride the
    SSWU sqrt_ratio scan."""
    shape = xm[0].shape
    bc2 = lambda c: jax.tree.map(lambda t: jnp.broadcast_to(t, shape), c)
    gamma = T.fp2_mul(e, y2)
    y, ok = None, None
    for m in range(4):
        c = gamma if m == 0 else T.fp2_mul(gamma, bc2(_SQR_MULTS_G2[m]))
        hit = T.fp2_eq(T.fp2_sqr(c), y2)
        y = c if y is None else T.fp2_select(hit, c, y)
        ok = hit if ok is None else (ok | hit)
    c1_zero = L.is_zero(L.from_mont(y[1]))
    larger = jnp.where(c1_zero, _fp_ge_half1(y[0]), _fp_ge_half1(y[1]))
    flip = larger ^ (sign_bit == 1)
    y = T.fp2_select(flip, T.fp2_neg(y), y)
    return (xm, y, T.fp2_ones(xm[0].shape[:-1])), ok


def g2_recover_y(x0_can, x1_can, sign_bit):
    xm, y2 = _g2_y2(x0_can, x1_can)
    e = T.fp2_pow_fixed(y2, _E2_EXP)
    return _g2_recover_post(xm, y2, e, sign_bit)


def g2_decompress_and_hash(sig_x0, sig_x1, sign_bit, u0, u1):
    """Fused G2 front end: signature decompression + hash_to_curve(u0, u1)
    with ONE Fp2 E2 = (p²-9)/16 pow scan across all three chains (width 3N)
    — the G2 mirror of g1_decompress_and_hash, serving the default
    pedersen-bls-chained/-unchained schemes (crypto/schemes.go:90-164).

    Returns (sig_jac, parse_ok, hm_jac)."""
    u = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), u0, u1)
    w, ctx = _sswu_g2_pre(u)
    xm, y2 = _g2_y2(sig_x0, sig_x1)
    stacked = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), w, y2)
    e = T.fp2_pow_fixed(stacked, _E2_EXP)
    n2 = u[0].shape[0]
    e_s = jax.tree.map(lambda t: t[:n2], e)
    e_d = jax.tree.map(lambda t: t[n2:], e)
    q = _iso_g2_proj(*_sswu_g2_post(e_s, ctx))
    sig_jac, ok = _g2_recover_post(xm, y2, e_d, sign_bit)
    n = u0[0].shape[0]
    q0 = jax.tree.map(lambda t: t[:n], q)
    q1 = jax.tree.map(lambda t: t[n:], q)
    hm = DC.g2_clear_cofactor(DC.G2_DEV.add(q0, q1))
    return sig_jac, ok, hm


def _fp_ge_half1(y_mont):
    """canonical(y) > (p-1)/2  ==  canonical(y) >= (p+1)/2."""
    y_can = L.from_mont(y_mont)
    return L.ge(y_can, jnp.broadcast_to(_HALF1_DEV, y_can.shape))
