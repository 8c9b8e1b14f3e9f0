"""Batched threshold-partial verification on TPU (BASELINE config 3).

The reference verifies each incoming partial with two pairings on the CPU
(`tbls.VerifyPartial`, chain/beacon/node.go:150) — O(n) pairings per round
per node, its hottest call site.  Here a whole (rounds x slots) block is
collapsed into ONE Miller product via a per-signer random linear combination:

    forall (r,j):  e(-g1, S_rj) · e(pk_idx(rj), H_r) == 1
    ==>  e(-g1, sum_rj c_rj·S_rj) · prod_i e(pk_i, T_i) == 1
         with  T_i = sum over slots with idx==i of c_rj·H_r

sound except with probability ~2^-SECURITY_BITS.  pk_i = PubPoly.eval(i) is
evaluated once per group on the host (the polynomial is tiny); the Miller
product has (#distinct signers + 1) pairs.  On RLC failure, exact per-slot
pairing checks locate invalid partials.

Occupancy fast path (ISSUE 10, ported from the r4 G1/G2 verify machinery):

  * the host no longer decompresses partials point by point — wire bytes
    are split into x-limb arrays with pure numpy (`batch._wire_parse`) and
    the y recovery rides the SAME single sqrt_ratio pow scan as the two
    SSWU hash maps (`ops/h2c.g2_decompress_and_hash`; scans cost per
    step, not per lane — the G1/G2 free lunch, now on partials);
  * the RLC MSM uses the split-sampled GLV coefficients: ψ-split 4-way on
    G2 (32-step joint ladder) and φ-split 2-way on G1 (64-step), exactly
    like crypto/batch.py's verify pipelines, instead of a 128-step
    per-bit ladder.  Soundness is unchanged: coefficients are sampled
    directly in split form (injective; see batch._device_rlc_bits).

Slot layout: callers pass ragged per-round partial lists (wire format:
be16(index) || sig); rows are padded to the widest row and masked.
"""

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from . import tbls as HT
from .batch import (_NEG_G1, _NEG_G2, _device_rlc_bits, run_program,
                    _gen_sub, _rlc_keys, _wire_parse, _GEN_JAC_G1,
                    _GEN_JAC_G2, _GEN_SIGN_G1, _GEN_SIGN_G2, _GEN_X_G1,
                    _GEN_X_G2, FRONT_DIGEST, FRONT_FIELDS, _h2f_front,
                    h2f_device_default)
from .schemes import Scheme, GroupG2
from ..ops import curve as DC
from ..ops import h2c as DH
from ..ops import limbs as L
from ..ops import pairing as DP
from ..ops import sha256 as SHA


def _tile_rounds(tree_pt, k):
    """(r, ...) point -> (r*k, ...): slot (r, j) sees round r's value."""
    return jax.tree.map(lambda t: jnp.repeat(t, k, axis=0), tree_pt)


def _masked_sums(curve, pts, onehot):
    """Per-signer sums: T_i = sum over slots with onehot[i]==1 (complete
    adds; masked-out slots become infinity).  Returns a stacked point
    tree with leading axis n_nodes.

    One `lax.scan` over the signer axis: the compiled graph contains a
    SINGLE masked sum tree instead of n_nodes unrolled copies.  The
    unrolled form made this the slowest-compiling program in the whole
    framework (>40 min cold XLA:CPU at 13 signers — it blew the bench's
    per-config watchdog on an idle core); the scan form is numerically
    identical and costs one extra sequential step per signer at runtime."""
    inf = curve.infinity((onehot.shape[1],))

    def body(carry, row):
        sel = curve._select(row == 1, pts, inf)
        return carry, curve.sum_points(sel)

    _, ts = jax.lax.scan(body, 0, onehot)
    return ts


def _prepend_point(single, stacked):
    """Prepend one unbatched point to a (k, ...)-stacked point tree."""
    return jax.tree.map(lambda s, t: jnp.concatenate([s[None], t], 0),
                        single, stacked)


def _partials_verdict(sub_ok, ok, valid):
    """Fused device scalar: RLC ok AND every valid slot's decompression +
    subgroup check ok (a slot that failed device decompression has a
    generator substitute and a live coefficient, so the RLC itself also
    fails — the fallback then localizes it)."""
    return ok & jnp.all(sub_ok | ~valid.astype(bool))


# lane concatenation shares ops/curve's helper (the psi-lane layout there
# is exactly this operation)
_cat = DC._cat_lanes


def _rlc_partials_run_g2sig(sig_x, sign, u0, u1, keys, valid, onehot,
                            pk_sel, neg_g1_aff):
    """sigs on G2, pks on G1.  sig_x: ((rk,24),(rk,24)) wire x limbs;
    sign: (rk,) flags; u0/u1: (r,) fp2; keys: (2, 2) threefry keys;
    valid: (rk,) slot mask; onehot: (p, rk); pk_sel: ((p,24),(p,24)) G1
    affine.  Front end: ONE Fp2 sqrt_ratio scan fuses slot decompression
    + both SSWU maps; MSM: ψ-split 4-way GLV over [S, ψS, H, ψH] lanes
    (32-step joint ladder, coefficients sampled as base-x quarters)."""
    rk = onehot.shape[1]
    r = u0[0].shape[0]
    k = rk // r
    sig_jac, parse_ok, hm_r = DH.g2_decompress_and_hash(
        sig_x[0], sig_x[1], sign, u0, u1)
    sig_jac = _gen_sub(DC.G2_DEV, _GEN_JAC_G2, sig_jac, parse_ok)
    sub_ok = DC.g2_in_subgroup(sig_jac) & parse_ok
    hm = _tile_rounds(hm_r, k)
    b0, b1, b2, b3 = _device_rlc_bits(keys, valid, split=4)
    # lane order [S, ψS, H, ψH]: the same coefficient c_rj multiplies
    # S_rj and H_r (the RLC identity), so both halves share the quarters
    base = _cat(sig_jac, DC.g2_psi(sig_jac), hm, DC.g2_psi(hm))
    bl = jnp.concatenate([b0, b1, b0, b1], axis=1)
    bh = jnp.concatenate([b2, b3, b2, b3], axis=1)
    mult = DC.g2_glv_msm_terms(base, bl, bh)
    s_sum = DC.G2_DEV.sum_points(jax.tree.map(lambda t: t[:2 * rk], mult))
    ch = jax.tree.map(lambda t: t[2 * rk:], mult)
    onehot2 = jnp.concatenate([onehot, onehot], axis=1)
    ts = _masked_sums(DC.G2_DEV, ch, onehot2)
    qx_all, qy_all, _ = DC.G2_DEV.to_affine(_prepend_point(s_sum, ts))
    px = jnp.concatenate([neg_g1_aff[0][None], pk_sel[0]], axis=0)
    py = jnp.concatenate([neg_g1_aff[1][None], pk_sel[1]], axis=0)
    ok = DP.paired_product_is_one(px, py, (qx_all, qy_all),
                                  onehot.shape[0] + 1)
    return sub_ok, _partials_verdict(sub_ok, ok, valid)


def _rlc_partials_run_g1sig(sig_x, sign, u0, u1, keys, valid, onehot,
                            pk_sel, neg_g2_aff):
    """sigs on G1, pks on G2 (short-sig scheme): fused decompression via
    the shared (p-3)/4 scan + φ-split 2-way GLV (64-step joint ladder)."""
    rk = onehot.shape[1]
    r = u0.shape[0]
    k = rk // r
    sig_jac, parse_ok, hm_r = DH.g1_decompress_and_hash(sig_x, sign, u0, u1)
    sig_jac = _gen_sub(DC.G1_DEV, _GEN_JAC_G1, sig_jac, parse_ok)
    sub_ok = DC.g1_in_subgroup(sig_jac) & parse_ok
    hm = _tile_rounds(hm_r, k)
    b0, b1 = _device_rlc_bits(keys, valid, split=2)
    both = _cat(sig_jac, hm)
    bits0 = jnp.concatenate([b0, b0], axis=1)
    bits1 = jnp.concatenate([b1, b1], axis=1)
    mult = DC.g1_glv_msm_terms(both, bits0, bits1)
    s_sum = DC.G1_DEV.sum_points(jax.tree.map(lambda t: t[:rk], mult))
    ch = jax.tree.map(lambda t: t[rk:], mult)
    ts = _masked_sums(DC.G1_DEV, ch, onehot)
    px_all, py_all, _ = DC.G1_DEV.to_affine(_prepend_point(s_sum, ts))
    qx = jax.tree.map(lambda a, b: jnp.concatenate([a[None], b], axis=0),
                      neg_g2_aff[0], pk_sel[0])
    qy = jax.tree.map(lambda a, b: jnp.concatenate([a[None], b], axis=0),
                      neg_g2_aff[1], pk_sel[1])
    ok = DP.paired_product_is_one(px_all, py_all, (qx, qy),
                                  onehot.shape[0] + 1)
    return sub_ok, _partials_verdict(sub_ok, ok, valid)


def _exact_partials_run_g2sig(sig_x, sign, u0, u1, pk_slot, neg_g1_aff):
    """Per-slot exact checks with per-slot pubkeys (fallback path); the
    decompression rides the same fused front end as the RLC pass."""
    rk = sig_x[0].shape[0]
    r = u0[0].shape[0]
    k = rk // r
    sig_jac, parse_ok, hm_r = DH.g2_decompress_and_hash(
        sig_x[0], sig_x[1], sign, u0, u1)
    sig_jac = _gen_sub(DC.G2_DEV, _GEN_JAC_G2, sig_jac, parse_ok)
    sub_ok = DC.g2_in_subgroup(sig_jac) & parse_ok
    hm = _tile_rounds(hm_r, k)
    sx, sy, s_inf = DC.G2_DEV.to_affine(sig_jac)
    hx, hy, _ = DC.G2_DEV.to_affine(hm)
    px = jnp.stack([jnp.broadcast_to(neg_g1_aff[0], (rk, L.NLIMB)), pk_slot[0]])
    py = jnp.stack([jnp.broadcast_to(neg_g1_aff[1], (rk, L.NLIMB)), pk_slot[1]])
    qx = jax.tree.map(lambda a, b: jnp.stack([a, b]), sx, hx)
    qy = jax.tree.map(lambda a, b: jnp.stack([a, b]), sy, hy)
    ok = DP.paired_product_is_one(px, py, (qx, qy), 2)
    return sub_ok & ~s_inf & ok


def _exact_partials_run_g1sig(sig_x, sign, u0, u1, pk_slot, neg_g2_aff):
    rk = sig_x.shape[0]
    r = u0.shape[0]
    k = rk // r
    sig_jac, parse_ok, hm_r = DH.g1_decompress_and_hash(sig_x, sign, u0, u1)
    sig_jac = _gen_sub(DC.G1_DEV, _GEN_JAC_G1, sig_jac, parse_ok)
    sub_ok = DC.g1_in_subgroup(sig_jac) & parse_ok
    hm = _tile_rounds(hm_r, k)
    sx, sy, s_inf = DC.G1_DEV.to_affine(sig_jac)
    hx, hy, _ = DC.G1_DEV.to_affine(hm)
    px = jnp.stack([sx, hx])
    py = jnp.stack([sy, hy])
    bc = lambda c: jnp.broadcast_to(c, (rk, L.NLIMB))
    qx = jax.tree.map(lambda a, b: jnp.stack([bc(a), b]), neg_g2_aff[0], pk_slot[0])
    qy = jax.tree.map(lambda a, b: jnp.stack([bc(a), b]), neg_g2_aff[1], pk_slot[1])
    ok = DP.paired_product_is_one(px, py, (qx, qy), 2)
    return sub_ok & ~s_inf & ok


@lru_cache(maxsize=None)
def _rlc_pipeline(g2sig: bool, front: str = FRONT_FIELDS, dst: bytes = b""):
    # front resolver shared with the beacon pipelines (batch._h2f_front):
    # "fields" passes the host-expanded (u0, u1) through, "digest" ships
    # the per-round 32-byte digests as words and runs expand_message_xmd
    # + hash_to_field ON DEVICE inside the same dispatch (ISSUE 14)
    core = _rlc_partials_run_g2sig if g2sig else _rlc_partials_run_g1sig
    h2f = _h2f_front(g2sig, front, dst)

    def run(sig_x, sign, msg, keys, valid, onehot, pk_sel, fixed_aff):
        u0, u1 = h2f(msg)
        return core(sig_x, sign, u0, u1, keys, valid, onehot, pk_sel,
                    fixed_aff)

    return jax.jit(run)


@lru_cache(maxsize=None)
def _exact_pipeline(g2sig: bool, front: str = FRONT_FIELDS,
                    dst: bytes = b""):
    core = _exact_partials_run_g2sig if g2sig else _exact_partials_run_g1sig
    h2f = _h2f_front(g2sig, front, dst)

    def run(sig_x, sign, msg, pk_slot, fixed_aff):
        u0, u1 = h2f(msg)
        return core(sig_x, sign, u0, u1, pk_slot, fixed_aff)

    return jax.jit(run)


class BatchPartialVerifier:
    """Verifies (round, slot) blocks of threshold partials for one group."""

    def __init__(self, scheme: Scheme, pub_poly: HT.PubPoly, n_nodes: int):
        self.scheme = scheme
        self.g2sig = scheme.sig_group is GroupG2
        self.n_nodes = n_nodes
        # every node's public share, once per group: ONE device dispatch
        # at committee scale (crypto/dkg_device.eval_all primes the
        # PubPoly memo so the evals below are lookups), host Horner below
        # the lane threshold — where n·t scalar muls are cheaper than a
        # dispatch
        from . import dkg_device
        if dkg_device.use_device(n_nodes):
            dkg_device.prime_public_shares(pub_poly, n_nodes)
        self.pub_points = [pub_poly.eval(i) for i in range(n_nodes)]
        if self.g2sig:
            # pks on G1
            self.pk_x = np.stack([np.asarray(L.encode_mont(p[0])) for p in self.pub_points])
            self.pk_y = np.stack([np.asarray(L.encode_mont(p[1])) for p in self.pub_points])
            self.fixed_aff = (L.encode_mont(_NEG_G1[0]), L.encode_mont(_NEG_G1[1]))
        else:
            # pks on G2: nested ((x0,x1),(y0,y1)) limb stacks
            enc = lambda sel: np.stack([np.asarray(L.encode_mont(sel(p))) for p in self.pub_points])
            self.pk_x = (enc(lambda p: p[0][0]), enc(lambda p: p[0][1]))
            self.pk_y = (enc(lambda p: p[1][0]), enc(lambda p: p[1][1]))
            self.fixed_aff = ((L.encode_mont(_NEG_G2[0][0]), L.encode_mont(_NEG_G2[0][1])),
                              (L.encode_mont(_NEG_G2[1][0]), L.encode_mont(_NEG_G2[1][1])))

    # -- host-side packing ---------------------------------------------------

    def _parse(self, rows, k):
        """-> (x limb array, sign flags, slot indices (r,k), valid (r,k)),
        all pure numpy — NO per-point host decompression (the y recovery
        runs on device inside the fused pipelines).  Host-detectable
        badness (missing slot, wrong length, bad flags, x >= p, signer
        index out of range) lands in the valid mask; slots whose x has no
        y on the curve are caught by the device parse_ok and localized by
        the exact fallback."""
        nb = 96 if self.g2sig else 48
        sig_bytes, idxs, idx_ok = [], [], []
        for row in rows:
            for j in range(k):
                p = bytes(row[j]) if j < len(row) and row[j] is not None \
                    else b""
                idx = HT.index_of(p) if len(p) >= 2 else 0
                if len(p) != nb + 2 or not (0 <= idx < self.n_nodes):
                    sig_bytes.append(b"")       # wrong length -> wire bad
                    idxs.append(0)
                    idx_ok.append(False)
                    continue
                sig_bytes.append(p[2:])
                idxs.append(idx)
                idx_ok.append(True)
        xw, sign, bad = _wire_parse(sig_bytes, self.g2sig)
        bad |= ~np.asarray(idx_ok)
        # substitute the generator encoding into bad slots: inert (zero
        # RLC coefficient, verdict carried by the valid mask)
        gx = _GEN_X_G2 if self.g2sig else _GEN_X_G1
        gsign = _GEN_SIGN_G2 if self.g2sig else _GEN_SIGN_G1
        xw[bad] = gx
        sign[bad] = gsign
        idxa = np.array(idxs)
        idxa[bad] = 0
        shape = (len(rows), k)
        return xw, sign, idxa.reshape(shape), (~bad).reshape(shape)

    def _sig_x(self, xw):
        if self.g2sig:
            return (jnp.asarray(xw[:, 0]), jnp.asarray(xw[:, 1]))
        return jnp.asarray(xw)

    def _msg_enc(self, msgs):
        """(front, msg pytree) for a round-digest list: above the h2f
        threshold the 32-byte digests ship as raw words and expand on
        device (the caller computed them once per ROUND, not per slot —
        the per-message xmd loop is what moves off-host); below it the
        host hash-to-field oracle runs unchanged."""
        if h2f_device_default(len(msgs)) \
                and all(len(m) == 32 for m in msgs):
            return FRONT_DIGEST, (jnp.asarray(
                SHA.pack_msgs_to_words(msgs, 32)),)
        if self.g2sig:
            return FRONT_FIELDS, DH.hash_msgs_to_field_g2(msgs,
                                                          self.scheme.dst)
        return FRONT_FIELDS, DH.hash_msgs_to_field_g1(msgs,
                                                      self.scheme.dst)

    def _pk_sel(self, signer_list):
        ix = np.asarray(signer_list)
        if self.g2sig:
            return (jnp.asarray(self.pk_x[ix]), jnp.asarray(self.pk_y[ix]))
        sel = lambda pair: (jnp.asarray(pair[0][ix]), jnp.asarray(pair[1][ix]))
        return (sel(self.pk_x), sel(self.pk_y))

    # -- verification --------------------------------------------------------

    def verify_partials(self, msgs, partial_rows) -> np.ndarray:
        """msgs: one digest per round; partial_rows: ragged per-round lists of
        wire partials (be16(index) || sig).  Returns an (r, kmax) validity
        mask (padded slots are False)."""
        r = len(msgs)
        if r == 0:
            return np.zeros((0, 0), dtype=bool)
        k = max((len(row) for row in partial_rows), default=0)
        if k == 0:
            return np.zeros((r, 0), dtype=bool)
        xw, sign, idxs, valid = self._parse(partial_rows, k)
        if not valid.any():
            return valid  # nothing parsed — no device work to do
        sig_x = self._sig_x(xw)
        sign_d = jnp.asarray(sign)
        front, msg = self._msg_enc(msgs)

        flat_valid = valid.reshape(-1)
        flat_idx = idxs.reshape(-1)
        signers = sorted(set(flat_idx[flat_valid]))
        onehot = np.zeros((len(signers), r * k), dtype=np.uint32)
        for i, s in enumerate(signers):
            onehot[i] = (flat_idx == s) & flat_valid
        # per-slot randomizers are sampled on device from a fresh 128-bit
        # key (batch._device_rlc_bits); invalid slots get zero coefficients
        _, all_ok = run_program(
            _rlc_pipeline(self.g2sig, front, self.scheme.dst),
            sig_x, sign_d, msg, jnp.asarray(_rlc_keys()),
            jnp.asarray(flat_valid.astype(np.uint32)), jnp.asarray(onehot),
            self._pk_sel(signers), self.fixed_aff,
            name=f"{'g2' if self.g2sig else 'g1'}_partials_rlc.{front}")
        if bool(all_ok):
            return valid

        # exact fallback: per-slot pairings with per-slot public shares
        pk_slot = self._pk_sel(idxs.reshape(-1))
        got = np.asarray(run_program(
            _exact_pipeline(self.g2sig, front, self.scheme.dst),
            sig_x, sign_d, msg, pk_slot, self.fixed_aff,
            name=f"{'g2' if self.g2sig else 'g1'}_partials_exact.{front}"))
        return got.reshape(r, k) & valid
