"""Batched threshold-partial verification on the device.

The reference verifies each incoming partial with two pairings on the CPU
(`tbls.VerifyPartial`, chain/beacon/node.go:150) — O(n) pairings per round
per node, its hottest call site.  Here a whole (rounds x slots) block is
collapsed into ONE Miller product via a per-slot random linear combination:

    forall (r,j):  e(-g1, S_rj) · e(pk_idx(rj), H_r) == 1
    ==>  e(-g1, sum_rj c_rj·S_rj) · prod_i e(pk_i, T_i) == 1
         with  T_i = sum over slots with idx==i of c_rj·H_r

sound except with probability ~2^-SECURITY_BITS.  pk_i = PubPoly.eval(i) is
evaluated once per group on the host (the polynomial is tiny).

Fixed shapes: a group of n nodes dispatches ONE program per front and
round count, whatever arrives.  Each round's slot axis is padded to n (a
multiple of n for a longer row), the signer axis is all n nodes, and a
signer with no live slot gives T_i = infinity, whose pair is made inert
(P = (0, 0) with a finite Q: the Miller value then lies in Fp2, which the
final exponentiation maps to 1).  So a round that verifies 1, 6 or 10
partials, from any signer subset, reuses the first call.

A failing block is localised with the SAME program: slots whose point
failed decompression or the subgroup check are dropped at once, and the
rest is bisected by re-running the check over halves of the slot mask.
A one-slot mask with a nonzero coefficient is an exact check of that
slot; a passing half next to a failing whole convicts the other half
without a pass of its own.

Occupancy fast path (ported from the G1/G2 verify machinery):

  * the host does not decompress partials point by point — wire bytes
    are split into x-limb arrays with pure numpy (`batch._wire_parse`) and
    the y recovery rides the SAME single sqrt_ratio pow scan as the two
    SSWU hash maps (`ops/h2c.g2_decompress_and_hash`; scans cost per
    step, not per lane);
  * the RLC MSM uses the split-sampled GLV coefficients: ψ-split 4-way on
    G2 (32-step joint ladder) and φ-split 2-way on G1 (64-step), exactly
    like crypto/batch.py's verify pipelines, instead of a 128-step
    per-bit ladder.  Soundness is unchanged: coefficients are sampled
    directly in split form (injective; see batch._device_rlc_bits).

Slot layout: callers pass ragged per-round partial lists (wire format:
be16(index) || sig); rows are padded to the slot width and masked.
"""

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from .. import metrics
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
    """Fused device scalar: RLC ok AND every masked-in slot's decompression
    + subgroup check ok (a slot that failed device decompression has a
    generator substitute and a live coefficient, so the RLC itself also
    fails — localisation then drops it by its `sub_ok`)."""
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
    qx_all, qy_all, q_inf = DC.G2_DEV.to_affine(_prepend_point(s_sum, ts))
    px = jnp.concatenate([neg_g1_aff[0][None], pk_sel[0]], axis=0)
    py = jnp.concatenate([neg_g1_aff[1][None], pk_sel[1]], axis=0)
    # e(P, infinity) = 1, but the Miller loop needs a finite Q: such a
    # pair (a signer with no live slot) becomes P = (0, 0) against the
    # generator, whose Miller value lies in Fp2 and exponentiates to 1
    px = L.select(q_inf, jnp.zeros_like(px), px)
    py = L.select(q_inf, jnp.zeros_like(py), py)
    qx_all, qy_all = (jax.tree.map(
        lambda q, g: L.select(q_inf, jnp.broadcast_to(g, q.shape), q), qc, gc)
        for qc, gc in ((qx_all, _GEN_JAC_G2[0]), (qy_all, _GEN_JAC_G2[1])))
    ok = DP.paired_product_is_one(px, py, (qx_all, qy_all),
                                  onehot.shape[0] + 1)
    return sub_ok, _partials_verdict(sub_ok, ok, valid)


def _rlc_partials_run_g1sig(sig_x, sign, u0, u1, keys, valid, onehot,
                            pk_sel, neg_g2_aff):
    """sigs on G1, pks on G2 (short-sig scheme): fused decompression via
    the shared (p-3)/4 scan + φ-split 2-way GLV (64-step joint ladder).
    A signer with no live slot has T_i = infinity, whose affine form
    (0, 0) is already an inert P against its finite key."""
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
        y on the curve are caught by the device parse_ok (`sub_ok`)."""
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
        mask (padded slots are False).  Each round's slots are padded to
        the group size, so the program's shape depends on the round count
        alone."""
        r = len(msgs)
        if r == 0:
            return np.zeros((0, 0), dtype=bool)
        kmax = max((len(row) for row in partial_rows), default=0)
        if kmax == 0:
            return np.zeros((r, 0), dtype=bool)
        k = -(-kmax // self.n_nodes) * self.n_nodes
        xw, sign, idxs, valid = self._parse(partial_rows, k)
        good = np.zeros(r * k, dtype=bool)
        ids = np.flatnonzero(valid.reshape(-1))
        if ids.size:
            check = self._checker(msgs, xw, sign, idxs.reshape(-1))
            ok, sub_ok = check(ids)
            if ok:
                good[ids] = True
            else:
                live = ids[sub_ok[ids]]
                _localise(check, live, live.size == ids.size, good)
        out = good.reshape(r, k)[:, :kmax]
        bad = sum(len(row) for row in partial_rows) - int(out.sum())
        if bad:
            metrics.add("partials.invalid", count=bad)
        return out

    def _checker(self, msgs, xw, sign, flat_idx):
        """-> check(slot ids) -> (RLC verdict over those slots, per-slot
        decompression + subgroup flags): one device pass of the block's
        program, with fresh coefficients, over the given slots."""
        front, msg = self._msg_enc(msgs)
        pipe = _rlc_pipeline(self.g2sig, front, self.scheme.dst)
        name = f"{'g2' if self.g2sig else 'g1'}_partials_rlc.{front}"
        # every node is a signer row; a masked-out slot's coefficient is 0
        onehot = jnp.asarray((flat_idx[None, :] == np.arange(
            self.n_nodes)[:, None]).astype(np.uint32))
        block = (self._sig_x(xw), jnp.asarray(sign), msg)
        pk_all = self._pk_sel(np.arange(self.n_nodes))

        def check(ids):
            mask = np.zeros(flat_idx.size, dtype=np.uint32)
            mask[ids] = 1
            metrics.add("partials.pass")
            sub_ok, ok = run_program(
                pipe, *block, jnp.asarray(_rlc_keys()), jnp.asarray(mask),
                onehot, pk_all, self.fixed_aff, name=name)
            return bool(ok), np.asarray(sub_ok)

        return check


def _localise(check, ids, failing: bool, good: np.ndarray) -> None:
    """Set `good` for the valid slots among `ids` by halves of the mask.
    `failing`: the check over `ids` is known to fail.  A valid slot never
    fails a check, so a failing whole whose first half passes has an
    invalid slot in its second half."""
    if ids.size == 0:
        return
    if not failing:
        if check(ids)[0]:
            good[ids] = True
            return
    if ids.size == 1:
        return
    lo, hi = ids[:ids.size // 2], ids[ids.size // 2:]
    if check(lo)[0]:
        good[lo] = True
        _localise(check, hi, True, good)
    else:
        _localise(check, lo, True, good)
        _localise(check, hi, False, good)
