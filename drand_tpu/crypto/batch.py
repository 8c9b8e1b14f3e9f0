"""Batched beacon verification / signing / tBLS recovery on TPU.

This is the framework's first-class new op (SURVEY.md §7 stage 2): the
reference verifies beacons one CPU pairing at a time
(client/verify.go:139-160 chain catch-up; chain/beacon/sync_manager.go:406
sync streams; chainstore.go:202-207 partial recovery) — here whole batches
run as one XLA program, and N verification equations are collapsed to a
single 2-pairing check via a random linear combination:

    forall i:  e(-g1, S_i) · e(pk, H_i) == 1
    ==>  e(-g1, sum r_i·S_i) · e(pk, sum r_i·H_i) == 1      (r_i random)

which is sound except with probability ~2^-SECURITY_BITS, because pk is the
same point for every round of a chain.  On RLC failure we fall back to exact
per-round pairing checks to locate the bad rounds.

Host/device split (this is a single-host-core environment — per-element
Python or C is the bottleneck): SHA-256 digests / hash-to-field run in one
threadable native C call; wire signatures are split into limb arrays with
pure numpy; the y-coordinate recovery (the sqrt of decompression) runs ON
DEVICE inside the pipelines, batched through the Pallas pow kernel.  All
curve/pairing algebra is device-side.  Batch sizes are padded to powers of
two to bound recompiles.
"""

import os
import secrets
import threading

from functools import lru_cache

import jax
import numpy as np

from .. import metrics
from ..log import Logger
from .host import curve as C
from .host import serialize as S
from .host.params import P, R, G1_GEN, G2_GEN
from .schemes import Scheme, GroupG1, GroupG2
from . import tbls as HT
from ..ops import curve as DC
from ..ops import h2c as DH
from ..ops import limbs as L
from ..ops import pairing as DP
from ..ops import sha256 as SHA

SECURITY_BITS = 128  # RLC randomizer width
_MIN_BATCH = 8

# -- occupancy knobs (ISSUE 10) ---------------------------------------------
# Depth of the dispatch pipeline: how many chunks are kept enqueued on the
# device AHEAD of the resolve point, so the per-dispatch latency amortizes
# across k dispatches instead of being paid serially per chunk.
# 1 == the r5 double buffer (pack k+1 overlaps device k, one dispatch deep).
DEFAULT_PIPELINE_DEPTH = max(1, int(os.environ.get(
    "DRAND_VERIFY_PIPELINE_DEPTH", "1")))
# Hard cap on in-flight bytes so depth x chunk footprint cannot blow device
# memory: the depth is clamped to INFLIGHT_BUDGET // chunk_footprint_bytes.
INFLIGHT_BUDGET_BYTES = int(float(os.environ.get(
    "DRAND_VERIFY_INFLIGHT_BUDGET_MB", "64")) * (1 << 20))

# -- device hash-to-field (ISSUE 14) ----------------------------------------
# Message-front modes for the verify pipelines.  The steady-state pack
# path ships RAW fixed-width message bytes and the whole digest +
# expand_message_xmd + hash_to_field chain runs inside the same dispatch
# (ops/h2c.py device stages); "fields" is the legacy host-expanded
# encoding — kept as the parity oracle and the below-threshold fallback;
# "digest" ships host-computed 32-byte digests and expands on device
# (irregular chained chunks — e.g. the genesis-seed slot's non-signature
# previous_sig — and the partials rows, whose digests the caller already
# holds).
FRONT_FIELDS = "fields"
FRONT_DIGEST = "digest"
FRONT_RAW_UNCHAINED = "raw_unchained"
FRONT_RAW_CHAINED = "raw_chained"


def h2f_device_min_n() -> int:
    """Batch width at or above which packing ships raw message bytes and
    hash-to-field runs on device (DRAND_H2F_DEVICE_MIN_N; below it the
    host loop is cheaper than the extra traced hash stages)."""
    return int(os.environ.get("DRAND_H2F_DEVICE_MIN_N", "64"))


def h2f_device_default(width: int) -> bool:
    """Front selection for a `width`-lane program: DRAND_H2F_DEVICE=0
    forces the host oracle, =1 forces device, auto compares the width
    against the threshold.  Deterministic per width, so each compiled
    pad keeps exactly one front flavor."""
    mode = os.environ.get("DRAND_H2F_DEVICE", "auto")
    if mode == "0":
        return False
    if mode == "1":
        return True
    return width >= h2f_device_min_n()


def pack_seconds() -> float:
    """Process-wide host pack wall time (`pack_chunk`, the `verify.pack`
    span), delta-able like dispatch_count()."""
    return metrics.totals().get("verify.pack", (0, 0.0))[1]


def chunk_footprint_bytes(pad: int, g2sig: bool) -> int:
    """Device bytes of ONE packed chunk encoding (sig x limbs + sign flags
    + two hash-to-field elements), the unit the in-flight cap divides."""
    limb_bytes = 24 * 4
    per_lane = (2 * limb_bytes + 4 + 4 * limb_bytes) if g2sig \
        else (limb_bytes + 4 + 2 * limb_bytes)
    return pad * per_lane


def max_pipeline_depth(pad: int, g2sig: bool) -> int:
    """Depth ceiling derived from the per-chunk footprint: depth beyond
    this would hold more than INFLIGHT_BUDGET_BYTES of packed chunk
    encodings in flight."""
    return max(1, INFLIGHT_BUDGET_BYTES // max(1, chunk_footprint_bytes(
        pad, g2sig)))


# (pipeline, argument shapes/dtypes/placements) keys already called in this
# process: a new key's first call traces and compiles its program
_RAN: set = set()

# jax compile-path events a first call reports, by their short names
_FIRST_CALL_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
# .events: {short name: [count, seconds]} of the first call in progress
# on this thread (jax fires these events on the compiling thread)
_first_call = threading.local()
_log = Logger("drand.batch")


def _note_event(event, secs, **_kw):
    events = getattr(_first_call, "events", None)
    if events is not None:
        short = _FIRST_CALL_EVENTS.get(event)
        if short is not None:
            c = events.setdefault(short, [0, 0.0])
            c[0] += 1
            c[1] += secs


jax.monitoring.register_event_duration_secs_listener(_note_event)


def _arg_key(a):
    return (getattr(a, "shape", None), str(getattr(a, "dtype", type(a))),
            getattr(a, "sharding", None))


def run_program(pipe, *args, name: str = "program"):
    """Call a jitted pipeline and count the dispatch (`batch.dispatch`).
    The first call of a (pipeline, shapes, placements) key runs inside
    `device_pool.compiling()`, so the verify service's watchdog spares
    its compile, and inside the span `batch.first_call`.  Its flavour,
    `<name>@<width>` (the leading size of the first argument), gets the
    span's seconds under `batch.first_call/<flavour>`, the jax trace,
    lower, compile and cache-load events that fired on this thread
    inside it under `batch.first_call/<flavour>/<event>`, and one INFO
    line.  Dispatch is asynchronous, so the device work itself is judged
    as usual."""
    metrics.add("batch.dispatch")
    leaves = jax.tree.leaves(args)
    key = (pipe, tuple(_arg_key(a) for a in leaves))
    if key in _RAN:
        return pipe(*args)
    from .device_pool import compiling
    width = next((a.shape[0] for a in leaves if getattr(a, "ndim", 0)), 0)
    flavour = f"{name}@{width}"
    events = _first_call.events = {}
    try:
        with compiling(), metrics.span("batch.first_call",
                                       flavour=flavour) as s:
            out = pipe(*args)
    finally:
        _first_call.events = None
    _RAN.add(key)
    metrics.add(f"batch.first_call/{flavour}", s.seconds)
    for short, (n, secs) in events.items():
        metrics.add(f"batch.first_call/{flavour}/{short}", secs, count=n)
    _log.info("first call of a device program", flavour=flavour,
              seconds=f"{s.seconds:.3f}",
              **{f"{short}_s": f"{secs:.3f}"
                 for short, (_, secs) in events.items()})
    return out


def dispatch_count() -> int:
    """Process-wide count of jitted device-pipeline invocations issued by
    this module (and crypto/partials.py) — the CPU-backend observability
    hook the one-dispatch-recover acceptance test and bench assert on."""
    return metrics.totals().get("batch.dispatch", (0, 0.0))[0]

_NEG_G1 = C.G1.neg(G1_GEN)
_NEG_G2 = C.G2.neg(G2_GEN)

# Wire-parse constants: canonical (non-Montgomery) generator x limbs + sign
# flags for substituting malformed/padding slots, and p for range checks.
_can_limbs = lambda x: np.asarray(L.int_to_limbs(x))
_mont_limbs = lambda x: np.asarray(L.int_to_limbs(x * L.R_MONT % P))
_P_WORDS = _can_limbs(P)
_GEN_X_G1 = _can_limbs(G1_GEN[0])
_GEN_SIGN_G1 = np.uint32(S._y_is_larger_fp(G1_GEN[1]))
_GEN_X_G2 = np.stack([_can_limbs(G2_GEN[0][0]), _can_limbs(G2_GEN[0][1])])
_GEN_SIGN_G2 = np.uint32(S._y_is_larger_fp2(G2_GEN[1]))
# in-pipeline generator substitute (Montgomery Jacobian, z = 1)
_GEN_JAC_G1 = (_mont_limbs(G1_GEN[0]), _mont_limbs(G1_GEN[1]), _mont_limbs(1))
_GEN_JAC_G2 = ((_mont_limbs(G2_GEN[0][0]), _mont_limbs(G2_GEN[0][1])),
               (_mont_limbs(G2_GEN[1][0]), _mont_limbs(G2_GEN[1][1])),
               (_mont_limbs(1), _can_limbs(0)))


def _ge_p(limbs: np.ndarray) -> np.ndarray:
    """x >= p over (n, 24) little-endian limb arrays (host range check)."""
    diff = limbs.astype(np.int64) - _P_WORDS.astype(np.int64)[None]
    nz = diff != 0
    any_nz = nz.any(axis=1)
    top = 23 - np.argmax(nz[:, ::-1], axis=1)
    return np.where(any_nz, diff[np.arange(len(limbs)), top] > 0, True)


def _wire_parse(sigs, g2: bool):
    """Compressed wire signatures -> (x limb array, sign bits, bad mask),
    all pure numpy.  x: (n, 24) for G1, (n, 2, 24) [x0, x1] for G2."""
    n = len(sigs)
    nb = 96 if g2 else 48
    bad = np.zeros(n, dtype=bool)
    if all(len(s) == nb for s in sigs):
        arr = np.frombuffer(b"".join(bytes(s) for s in sigs),
                            np.uint8).reshape(n, nb).copy()
    else:
        arr = np.zeros((n, nb), np.uint8)
        for i, sig in enumerate(sigs):
            if len(sig) == nb:
                arr[i] = np.frombuffer(bytes(sig), np.uint8)
            else:
                bad[i] = True
    flags = arr[:, 0]
    bad |= (flags & 0x80) == 0
    bad |= (flags & 0x40) != 0                  # infinity: invalid signature
    sign = ((flags >> 5) & 1).astype(np.uint32)
    arr[:, 0] &= 0x1F

    def words(block):                           # 48 BE bytes -> 24 LE limbs
        w = (block[:, ::2].astype(np.uint32) << 8) | block[:, 1::2]
        return np.ascontiguousarray(w[:, ::-1])

    if g2:
        x1 = words(arr[:, :48])                 # wire order: c1 then c0
        x0 = words(arr[:, 48:])
        bad |= _ge_p(x0) | _ge_p(x1)
        return np.stack([x0, x1], axis=1), sign, bad
    x = words(arr)
    bad |= _ge_p(x)
    return x, sign, bad


def _pad_msgs(msgs, pad: int):
    """Pad a message list to `pad` entries; keeps lengths uniform when they
    already are (the native h2f batch path requires equal lengths)."""
    pad_msg = b"\x00" * len(msgs[0]) if msgs and \
        all(len(m) == len(msgs[0]) for m in msgs) else b""
    return list(msgs) + [pad_msg] * (pad - len(msgs))


def _pad_len(n: int) -> int:
    m = _MIN_BATCH
    while m < n:
        m *= 2
    return m


def _rlc_keys() -> "np.ndarray":
    """(2, 2) uint32: two independent 64-bit threefry keys (128 bits of key
    material total) for the on-device randomizer stream.

    The two streams are XORed on device, so EQUAL halves would cancel to an
    all-zero randomizer (every RLC coefficient 0 — the pairing check passes
    vacuously and per-batch soundness collapses to the 2^-64 collision
    probability).  Resample on collision: the degenerate event becomes
    impossible instead of astronomically unlikely."""
    raw = secrets.token_bytes(16)
    while raw[:8] == raw[8:]:
        raw = secrets.token_bytes(16)
    return np.frombuffer(raw, np.uint32).reshape(2, 2)


def _device_rlc_bits(keys, mask, split: int):
    """Uniform RLC randomizer bits generated ON DEVICE, inside the verify
    pipeline (r5: shipping the host-sampled (SECURITY_BITS, pad) uint32 bit
    planes cost ~4 MB of interconnect per 8192-round chunk — more bytes
    than the signatures themselves).  A single threefry2x32 key is only 64
    bits, so the stream is the XOR of two independently-keyed streams:
    predicting the randomizers requires both keys (2^-128 with distinct
    halves, which _rlc_keys enforces by resampling).  Lanes where `mask`
    is 0 get zero
    coefficients (inert pad / invalid slots).

    split=2 returns the coefficient in SAMPLED split form (b0, b1) with
    k = k0 + lambda*k1, k0/k1 uniform 64-bit (the G1 phi eigenvalue) —
    injective in (k0, k1), so per-coefficient soundness stays
    2^-SECURITY_BITS while the ladder runs 64 joint steps instead of 128.
    split=4 likewise samples k = k0 + x·k1 + x²·k2 + x³·k3 with uniform
    32-bit quarters (the G2 psi eigenvalue x; |x| > 2^32 makes the map
    injective by the base-x digit argument) — a 32-step joint ladder."""
    import jax.random as jr
    jnp = jax.numpy
    pad = mask.shape[0]
    nw = SECURITY_BITS // 32
    w = (jr.bits(jr.wrap_key_data(keys[0]), (nw, pad), jnp.uint32)
         ^ jr.bits(jr.wrap_key_data(keys[1]), (nw, pad), jnp.uint32))
    shifts = jnp.arange(31, -1, -1, dtype=jnp.uint32)
    bits = (w[:, None, :] >> shifts[None, :, None]) & jnp.uint32(1)
    bits = bits.reshape(SECURITY_BITS, pad)
    bits = bits * mask.astype(jnp.uint32)[None, :]
    part = SECURITY_BITS // split
    return tuple(bits[i * part:(i + 1) * part] for i in range(split))


# ---------------------------------------------------------------------------
# jitted pipelines (cached per signature-group kind; shapes are polymorphic
# across calls of the same padded size thanks to jit's shape cache)
# ---------------------------------------------------------------------------

def _gen_sub(curve, gen, pt, ok):
    """Replace slots whose decompression failed with the generator so they
    cannot poison the RLC; the returned ok mask carries the verdict."""
    shape = curve.f.batch_shape(curve._leaf(pt[0]))
    genb = jax.tree.map(
        lambda c: jax.numpy.broadcast_to(jax.numpy.asarray(c),
                                         shape + (L.NLIMB,)), gen)
    return curve._select(ok, pt, genb)


def _rlc_run_g2sig(sig_x, sign, u0, u1, keys, n, pk_aff, neg_g1_aff):
    """Scheme family with sigs on G2, keys on G1 (chained/unchained).

    Front end: ONE Fp2 sqrt_ratio scan fuses decompression + both SSWU
    maps (ops/h2c.py g2_decompress_and_hash).  MSM: psi-split 4-way GLV —
    the 128-bit coefficient is sampled as base-x quarters (b0..b3); lanes
    [S, psi(S), H, psi(H)] run a 32-step psi²-joint mixed ladder and the
    sum trees fold the psi lanes back in (A over the S-half, B over the
    H-half)."""
    sig_jac, parse_ok, hm = DH.g2_decompress_and_hash(
        sig_x[0], sig_x[1], sign, u0, u1)
    sig_jac = _gen_sub(DC.G2_DEV, _GEN_JAC_G2, sig_jac, parse_ok)
    sub_ok = DC.g2_in_subgroup(sig_jac) & parse_ok
    cat = lambda *ts: jax.numpy.concatenate(ts, 0)
    # lane order [S, psiS, H, psiH]: A sums the first half, B the second
    base = jax.tree.map(cat, sig_jac, DC.g2_psi(sig_jac),
                        hm, DC.g2_psi(hm))
    lane_mask = jax.numpy.arange(sub_ok.shape[0]) < n
    b0, b1, b2, b3 = _device_rlc_bits(keys, lane_mask, split=4)
    bl = jax.numpy.concatenate([b0, b1, b0, b1], axis=1)
    bh = jax.numpy.concatenate([b2, b3, b2, b3], axis=1)
    mult = DC.g2_glv_msm_terms(base, bl, bh)
    # `half` is the MSM lane-split width — do NOT shadow the traced round
    # count `n`, which _fused_verdict needs for real pad-lane masking
    half = 2 * b0.shape[1]
    A = DC.G2_DEV.sum_points(jax.tree.map(lambda t: t[:half], mult))
    B = DC.G2_DEV.sum_points(jax.tree.map(lambda t: t[half:], mult))
    ax, ay, _ = DC.G2_DEV.to_affine(A)
    bx, by, _ = DC.G2_DEV.to_affine(B)
    # stack the 2 pairs of the check into one Miller call
    px = jax.numpy.stack([neg_g1_aff[0], pk_aff[0]])
    py = jax.numpy.stack([neg_g1_aff[1], pk_aff[1]])
    qx = jax.tree.map(lambda a, b: jax.numpy.stack([a, b]), ax, bx)
    qy = jax.tree.map(lambda a, b: jax.numpy.stack([a, b]), ay, by)
    ok = DP.paired_product_is_one(px, py, (qx, qy), 2)
    return sub_ok, _fused_verdict(sub_ok, ok, n)


def _rlc_run_g1sig(sig_x, sign, u0, u1, keys, n, pk_aff, neg_g2_aff):
    """Short-sig scheme: sigs on G1, keys on G2."""
    sig_jac, parse_ok, hm = DH.g1_decompress_and_hash(sig_x, sign, u0, u1)
    sig_jac = _gen_sub(DC.G1_DEV, _GEN_JAC_G1, sig_jac, parse_ok)
    sub_ok = DC.g1_in_subgroup(sig_jac) & parse_ok
    both = jax.tree.map(lambda a, b: jax.numpy.concatenate([a, b], 0), sig_jac, hm)
    lane_mask = jax.numpy.arange(sub_ok.shape[0]) < n
    b0, b1 = _device_rlc_bits(keys, lane_mask, split=2)
    bits2 = (jax.numpy.concatenate([b0, b0], axis=1),
             jax.numpy.concatenate([b1, b1], axis=1))
    mult = DC.g1_glv_msm_terms(both, *bits2)
    half = b0.shape[1]      # MSM lane-split width; keep the traced `n` alive
    A = DC.G1_DEV.sum_points(jax.tree.map(lambda t: t[:half], mult))
    B = DC.G1_DEV.sum_points(jax.tree.map(lambda t: t[half:], mult))
    ax, ay, _ = DC.G1_DEV.to_affine(A)
    bx, by, _ = DC.G1_DEV.to_affine(B)
    # e(A, -g2) · e(B, pk) == 1
    px = jax.numpy.stack([ax, bx])
    py = jax.numpy.stack([ay, by])
    qx = jax.tree.map(lambda a, b: jax.numpy.stack([a, b]), neg_g2_aff[0], pk_aff[0])
    qy = jax.tree.map(lambda a, b: jax.numpy.stack([a, b]), neg_g2_aff[1], pk_aff[1])
    ok = DP.paired_product_is_one(px, py, (qx, qy), 2)
    return sub_ok, _fused_verdict(sub_ok, ok, n)


def _fused_verdict(sub_ok, ok, n):
    """Single device-side scalar: RLC ok AND every real lane's subgroup/
    parse check ok.  Folding the lane reduction into the pipeline leaves
    ONE tiny scalar readback per chunk instead of an (n,)-mask transfer +
    host reduction."""
    lanes = jax.numpy.arange(sub_ok.shape[0])
    return ok & jax.numpy.all(sub_ok | (lanes >= n))


def _exact_run_g2sig(sig_x, sign, u0, u1, pk_aff, neg_g1_aff):
    """Per-round exact check (fallback path): e(-g1,S_i)·e(pk,H_i) == 1."""
    sig_jac, parse_ok, hm = DH.g2_decompress_and_hash(
        sig_x[0], sig_x[1], sign, u0, u1)
    sig_jac = _gen_sub(DC.G2_DEV, _GEN_JAC_G2, sig_jac, parse_ok)
    sub_ok = DC.g2_in_subgroup(sig_jac) & parse_ok
    sx, sy, _ = DC.G2_DEV.to_affine(sig_jac)
    hx, hy, _ = DC.G2_DEV.to_affine(hm)
    n = u0[0].shape[0]
    px = jax.numpy.stack([jax.numpy.broadcast_to(neg_g1_aff[0], (n, L.NLIMB)),
                          jax.numpy.broadcast_to(pk_aff[0], (n, L.NLIMB))])
    py = jax.numpy.stack([jax.numpy.broadcast_to(neg_g1_aff[1], (n, L.NLIMB)),
                          jax.numpy.broadcast_to(pk_aff[1], (n, L.NLIMB))])
    qx = jax.tree.map(lambda a, b: jax.numpy.stack([a, b]), sx, hx)
    qy = jax.tree.map(lambda a, b: jax.numpy.stack([a, b]), sy, hy)
    ok = DP.paired_product_is_one(px, py, (qx, qy), 2)
    return sub_ok & ok


def _exact_run_g1sig(sig_x, sign, u0, u1, pk_aff, neg_g2_aff):
    sig_jac, parse_ok, hm = DH.g1_decompress_and_hash(sig_x, sign, u0, u1)
    sig_jac = _gen_sub(DC.G1_DEV, _GEN_JAC_G1, sig_jac, parse_ok)
    return parse_ok & _exact_g1sig_core(sig_jac, hm, pk_aff, neg_g2_aff)


def _exact_run_g1sig_jac(sig_jac, u0, u1, pk_aff, neg_g2_aff):
    """Exact per-round check with the signature already a device Jacobian
    point — the aggregation path (tBLS Recover, chainstore.go:202-207)
    produces recovered points directly, no wire decompression involved."""
    hm = DH.hash_to_g1_jac(u0, u1)
    return _exact_g1sig_core(sig_jac, hm, pk_aff, neg_g2_aff)


def _exact_run_g2sig_jac(sig_jac, u0, u1, pk_aff, neg_g1_aff):
    """G2-sig mirror of _exact_run_g1sig_jac (the default chained/unchained
    schemes' aggregation path)."""
    hm = DH.hash_to_g2_jac(u0, u1)
    sub_ok = DC.g2_in_subgroup(sig_jac)
    sx, sy, _ = DC.G2_DEV.to_affine(sig_jac)
    hx, hy, _ = DC.G2_DEV.to_affine(hm)
    n = u0[0].shape[0]
    px = jax.numpy.stack([jax.numpy.broadcast_to(neg_g1_aff[0], (n, L.NLIMB)),
                          jax.numpy.broadcast_to(pk_aff[0], (n, L.NLIMB))])
    py = jax.numpy.stack([jax.numpy.broadcast_to(neg_g1_aff[1], (n, L.NLIMB)),
                          jax.numpy.broadcast_to(pk_aff[1], (n, L.NLIMB))])
    qx = jax.tree.map(lambda a, b: jax.numpy.stack([a, b]), sx, hx)
    qy = jax.tree.map(lambda a, b: jax.numpy.stack([a, b]), sy, hy)
    ok = DP.paired_product_is_one(px, py, (qx, qy), 2)
    return sub_ok & ok


def _exact_g1sig_core(sig_jac, hm, pk_aff, neg_g2_aff):
    sub_ok = DC.g1_in_subgroup(sig_jac)
    sx, sy, _ = DC.G1_DEV.to_affine(sig_jac)
    hx, hy, _ = DC.G1_DEV.to_affine(hm)
    n = sx.shape[0]
    # e(S, -g2) · e(H_i, pk) == 1
    px = jax.numpy.stack([sx, hx])
    py = jax.numpy.stack([sy, hy])
    bc = lambda c: jax.numpy.broadcast_to(c, (n, L.NLIMB))
    qx = jax.tree.map(lambda a, b: jax.numpy.stack([bc(a), bc(b)]),
                      neg_g2_aff[0], pk_aff[0])
    qy = jax.tree.map(lambda a, b: jax.numpy.stack([bc(a), bc(b)]),
                      neg_g2_aff[1], pk_aff[1])
    ok = DP.paired_product_is_one(px, py, (qx, qy), 2)
    return sub_ok & ok


def _h2f_front(g2sig: bool, front: str, dst: bytes):
    """Static front resolver: message pytree -> (u0, u1) field elements
    inside the traced pipeline.  "fields" passes the host-expanded pair
    through; the device fronts run digest + expand_message_xmd +
    hash_to_field ON DEVICE (ops/h2c.py) — same dispatch, no extra
    program stage, `dispatch_count()` unchanged."""
    if front == FRONT_FIELDS:
        return lambda msg: msg

    def resolve(msg):
        if front == FRONT_DIGEST:
            dw = msg[0]
        else:
            dw = DH.beacon_digests_dev(msg)
        if g2sig:
            return DH.hash_to_field_fp2_dev(dw, 32, dst)
        return DH.hash_to_field_fp_dev(dw, 32, dst)

    return resolve


@lru_cache(maxsize=None)
def _rlc_pipeline_g2sig(front: str = FRONT_FIELDS, dst: bytes = b""):
    # `front`/`dst` are trace-time constants: each (front, dst) pair is its
    # own compiled flavor, selected deterministically per pad width.  The
    # streaming path and bisection share this one program: a separate
    # buffer-donating flavor cost a second multi-minute cold compile per
    # scheme for ~1 MB of chunk inputs it saved on the device.
    h2f = _h2f_front(True, front, dst)

    def run(sig_x, sign, msg, keys, n, pk_aff, neg_g1_aff):
        u0, u1 = h2f(msg)
        return _rlc_run_g2sig(sig_x, sign, u0, u1, keys, n, pk_aff,
                              neg_g1_aff)

    return jax.jit(run)


@lru_cache(maxsize=None)
def _rlc_pipeline_g1sig(front: str = FRONT_FIELDS, dst: bytes = b""):
    h2f = _h2f_front(False, front, dst)

    def run(sig_x, sign, msg, keys, n, pk_aff, neg_g2_aff):
        u0, u1 = h2f(msg)
        return _rlc_run_g1sig(sig_x, sign, u0, u1, keys, n, pk_aff,
                              neg_g2_aff)

    return jax.jit(run)


@lru_cache(maxsize=None)
def _rlc_pipeline_sharded(g2sig: bool, front: str, dst: bytes, mesh):
    """The RLC check of a batch split over a round-axis mesh.  Pallas
    kernels cannot be partitioned by XLA (the v5e:2x2 compile refuses
    the jit-with-shardings form, PR 21), so under `shard_map` every
    device runs the whole pipeline on its own lanes — with its own
    randomizers (keys folded with the shard index) — and the verdict is
    the AND over shards: an RLC per shard is as sound as one over the
    batch.  A shard holding no real lane (n within an earlier shard)
    passes."""
    jnp = jax.numpy
    P = jax.sharding.PartitionSpec
    h2f = _h2f_front(g2sig, front, dst)
    run = _rlc_run_g2sig if g2sig else _rlc_run_g1sig

    def shard(sig_x, sign, msg, keys, n, pk_aff, fixed_aff):
        i = jax.lax.axis_index("round").astype(jnp.uint32)
        w = jnp.uint32(sign.shape[0])
        n_i = jnp.clip(n.astype(jnp.int32) - (i * w).astype(jnp.int32),
                       0, sign.shape[0]).astype(jnp.uint32)
        u0, u1 = h2f(msg)
        sub_ok, ok = run(sig_x, sign, u0, u1, keys ^ i, n_i, pk_aff,
                         fixed_aff)
        ok = (ok | (n_i == 0)).astype(jnp.int32)
        return sub_ok, jax.lax.pmin(ok, "round") > 0

    rnd = P("round")
    # check_vma=False: the limb kernels seed scan carries with replicated
    # zeros, which the varying-manual-axes check rejects (the math is
    # per-lane pure) — as in __graft_entry__.dryrun_multichip
    return jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(rnd, rnd, rnd, P(), P(), P(), P()),
        out_specs=(rnd, P()), check_vma=False))


@lru_cache(maxsize=None)
def _exact_pipeline_g2sig(front: str = FRONT_FIELDS, dst: bytes = b""):
    h2f = _h2f_front(True, front, dst)

    def run(sig_x, sign, msg, pk_aff, neg_g1_aff):
        u0, u1 = h2f(msg)
        return _exact_run_g2sig(sig_x, sign, u0, u1, pk_aff, neg_g1_aff)

    return jax.jit(run)


@lru_cache(maxsize=None)
def _exact_pipeline_g1sig(front: str = FRONT_FIELDS, dst: bytes = b""):
    h2f = _h2f_front(False, front, dst)

    def run(sig_x, sign, msg, pk_aff, neg_g2_aff):
        u0, u1 = h2f(msg)
        return _exact_run_g1sig(sig_x, sign, u0, u1, pk_aff, neg_g2_aff)

    return jax.jit(run)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


class BatchBeaconVerifier:
    """TPU-batched verifier for one chain (fixed scheme + collective pubkey).

    The drand-side analogue would be the `BatchVerifyBeacon` extension of
    crypto.Scheme described in BASELINE.json's north star."""

    kind = "device"  # metrics label for integrity scans (chain/integrity.py)

    def __init__(self, scheme: Scheme, public_key_bytes: bytes,
                 pad_to: int | None = None, sharding=None, devices=None,
                 h2f_device: bool | None = None, widths=None):
        self.scheme = scheme
        self.g2sig = scheme.sig_group is GroupG2
        self._g = "g2" if self.g2sig else "g1"     # program flavour prefix
        # h2f_device: None = auto (per pad width vs DRAND_H2F_DEVICE_MIN_N);
        # True/False pin the front — the verify service pins per handle so
        # the compiled-program flavor set is fixed at handle creation
        self.h2f_device = h2f_device
        # pad_to: optional canonical batch width.  Batches pad UP to it so
        # differently-sized chains share one compiled program (the bench
        # pads every config to 8192: compile count is the scarce resource
        # on-chip, and pad slots cost ~linear device time but zero compiles)
        self.pad_to = pad_to
        # widths: the lane widths a batch may dispatch at (the verify
        # service's crypto/tuning.lane_widths, topped by pad_to); a batch
        # takes the smallest that holds it (lane_width).  Default: the one
        # width pad_to.
        self.widths = tuple(sorted(widths)) if widths \
            else (pad_to,) if pad_to else ()
        # sharding: optional persistent placement over the round axis,
        # owned by the caller (the verify service's device pool builds ONE
        # mesh per scope); devices: an explicit device group this verifier
        # is pinned to (crypto/device_pool.py) — its placement is built
        # once and cached.  With neither, a multi-device host gets a
        # cached all-device mesh (built on FIRST dispatch, not per
        # dispatch — the per-dispatch Mesh construction was pure overhead
        # on every multi-device dispatch).
        self.sharding = sharding
        self.devices = list(devices) if devices is not None else None
        self._cached_sharding = None
        self._sharding_built = False
        self._pin_sharding = None
        self.pub_point = scheme.key_group.from_bytes(public_key_bytes)
        if self.g2sig:
            self.pk_aff = (L.encode_mont(self.pub_point[0]), L.encode_mont(self.pub_point[1]))
            self.fixed_aff = (L.encode_mont(_NEG_G1[0]), L.encode_mont(_NEG_G1[1]))
        else:
            self.pk_aff = ((L.encode_mont(self.pub_point[0][0]), L.encode_mont(self.pub_point[0][1])),
                           (L.encode_mont(self.pub_point[1][0]), L.encode_mont(self.pub_point[1][1])))
            self.fixed_aff = ((L.encode_mont(_NEG_G2[0][0]), L.encode_mont(_NEG_G2[0][1])),
                              (L.encode_mont(_NEG_G2[1][0]), L.encode_mont(_NEG_G2[1][1])))

    def lane_width(self, n: int) -> int:
        """Lanes a batch of n rounds is packed and dispatched at: the
        smallest of `widths` that holds the next power of two, or that
        power of two when it is wider than every one of them."""
        need = _pad_len(n)
        return next((w for w in self.widths if w >= need), need)

    # -- host-side packing ---------------------------------------------------

    def _messages(self, rounds, prev_sigs):
        """Host digest_beacon loop — the FIELDS/DIGEST-front oracle and
        fallback only; the raw fronts ship (prevSig, round) words and
        digest on device (ops/h2c.beacon_digests_dev)."""
        if self.scheme.chained:
            # tpu-vet: disable=trace  (oracle/fallback, see docstring)
            return [self.scheme.digest_beacon(r, p)
                    for r, p in zip(rounds, prev_sigs)]
        # tpu-vet: disable=trace  (oracle/fallback, see docstring)
        return [self.scheme.digest_beacon(r, None) for r in rounds]

    def _encode(self, sigs, msgs, pad):
        """Host packing for the FIELDS front (the parity oracle /
        below-threshold path), O(1) Python ops: numpy wire parse (x limbs
        + sign flags; y recovery happens on device in the pipelines) and
        batched host hash-to-field.  Malformed and padding slots carry
        the generator encoding — inert (zero RLC coefficient / discarded
        exact result), with the verdict in the returned bad mask."""
        sig_x, sign, bad = self._encode_sigs(sigs, pad)
        pmsgs = _pad_msgs(msgs, pad)
        if self.g2sig:
            u0, u1 = DH.hash_msgs_to_field_g2(pmsgs, self.scheme.dst)
        else:
            u0, u1 = DH.hash_msgs_to_field_g1(pmsgs, self.scheme.dst)
        return (sig_x, sign, u0, u1), bad

    def _encode_sigs(self, sigs, pad):
        """The signature half of packing (shared by every front): numpy
        wire parse -> (sig_x device tensor(s), sign flags, bad mask)."""
        import jax.numpy as jnp
        n = len(sigs)
        xw, sign, bad = _wire_parse(sigs, self.g2sig)
        gx = _GEN_X_G2 if self.g2sig else _GEN_X_G1
        gsign = _GEN_SIGN_G2 if self.g2sig else _GEN_SIGN_G1
        xshape = (pad, 2, L.NLIMB) if self.g2sig else (pad, L.NLIMB)
        full_x = np.empty(xshape, np.uint32)
        full_sign = np.empty(pad, np.uint32)
        full_x[:n], full_sign[:n] = xw, sign
        full_x[:n][bad] = gx
        full_sign[:n][bad] = gsign
        full_x[n:] = gx
        full_sign[n:] = gsign
        if self.g2sig:
            sig_x = (jnp.asarray(full_x[:, 0]), jnp.asarray(full_x[:, 1]))
        else:
            sig_x = jnp.asarray(full_x)
        return sig_x, jnp.asarray(full_sign), bad

    @staticmethod
    def _round_words(rounds, pad) -> np.ndarray:
        """(pad, 2) uint32 BE words of the 8-byte big-endian rounds."""
        r = np.zeros(pad, np.uint64)
        r[:len(rounds)] = np.asarray([int(x) for x in rounds], np.uint64)
        return np.stack([(r >> 32).astype(np.uint32),
                         (r & 0xFFFFFFFF).astype(np.uint32)], axis=1)

    def _msg_front(self, rounds, prev_sigs, pad):
        """Build the device-h2f message pytree: raw fixed-width message
        words (pure numpy concatenation — the host pack stage does no
        hashing at all) when the chunk is uniform, else host digests
        shipped as words (the digest front: irregular chained chunks —
        a genesis-seed previous_sig is not signature-width).  Returns
        (front, msg)."""
        import jax.numpy as jnp
        rw = jnp.asarray(self._round_words(rounds, pad))
        if not self.scheme.chained:
            return FRONT_RAW_UNCHAINED, (rw,)
        plen = self.scheme.sig_group.point_len
        lens = {len(p) for p in prev_sigs if p}
        if lens <= {plen}:
            prev = np.zeros((pad, plen), np.uint8)
            has = np.zeros(pad, np.uint32)
            idx = [i for i, p in enumerate(prev_sigs) if p]
            if idx:
                # one bulk join + frombuffer, not a per-lane row assign:
                # the prev matrix is most of the chained pack term
                flat = np.frombuffer(
                    b"".join(bytes(prev_sigs[i]) for i in idx), np.uint8)
                prev[idx] = flat.reshape(len(idx), plen)
                has[idx] = 1
            pw = np.ascontiguousarray(
                prev.reshape(pad, plen // 4, 4).view(">u4")
                .reshape(pad, plen // 4).astype(np.uint32))
            return FRONT_RAW_CHAINED, (jnp.asarray(pw), rw, jnp.asarray(has))
        msgs = _pad_msgs(self._messages(rounds, prev_sigs), pad)
        dw = SHA.pack_msgs_to_words(msgs, 32)
        return FRONT_DIGEST, (jnp.asarray(dw),)

    def _pack_enc(self, rounds, sigs, prev_sigs, pad):
        """Front-aware packing -> ((sig_x, sign, msg), bad, front).  The
        front is resolved per PAD WIDTH (h2f_device_default, or the
        explicit `h2f_device=` ctor pin): each compiled pad keeps one
        flavor, and below the threshold the host oracle path runs
        unchanged."""
        use_dev = self.h2f_device if self.h2f_device is not None \
            else h2f_device_default(pad)
        if use_dev:
            sig_x, sign, bad = self._encode_sigs(sigs, pad)
            front, msg = self._msg_front(rounds, prev_sigs, pad)
            return (sig_x, sign, msg), bad, front
        msgs = self._messages(rounds, prev_sigs)
        (sig_x, sign, u0, u1), bad = self._encode(sigs, msgs, pad)
        return (sig_x, sign, (u0, u1)), bad, FRONT_FIELDS

    # -- verification ---------------------------------------------------------

    def _slice_enc(self, enc, lo, hi, width=None):
        """Slice the one-time batch encoding to [lo, hi), padded back to
        `width` lanes (default: the next power of two) with slots reused
        from the head of the batch — pad slots are inert (zero RLC
        coefficients; exact results discarded), so any well-formed slot
        serves.  Encoding once and slicing avoids re-hashing messages and
        re-encoding Montgomery limbs at every bisection level."""
        import jax.numpy as jnp
        padlen = width or _pad_len(hi - lo)
        extra = padlen - (hi - lo)

        def cut(t):
            if lo == 0 and t.shape[0] == padlen:
                return t                      # top level: already padded
            s = t[lo:hi]
            return jnp.concatenate([s, t[:extra]], axis=0) if extra else s

        return jax.tree.map(cut, enc)

    # below this batch width sharding is pure overhead: the SPMD-partitioned
    # pairing program compiles far slower and tiny shards leave devices idle
    SHARD_MIN_PAD = 512

    def _placement(self):
        """The persistent round-axis placement for this verifier, built
        ONCE and cached (via device_pool.build_round_sharding — the one
        construction site): the injected service sharding wins; an
        explicit device group (crypto/device_pool.py) pins to its
        devices; otherwise a multi-device host gets one cached
        all-device mesh.  None = no placement (single visible device,
        nothing to pin)."""
        if self.sharding is not None:
            return self.sharding
        if self._sharding_built:
            return self._cached_sharding
        from .device_pool import build_round_sharding, jax_devices
        devs = self.devices
        if devs is None:
            devs = jax_devices()
            if len(devs) < 2:
                devs = []       # default device; placement buys nothing
        self._cached_sharding = build_round_sharding(devs)
        self._sharding_built = True
        return self._cached_sharding

    def _pin_fallback(self, sh):
        """A multi-device sharding whose batch cannot be split cleanly
        still has to stay on ITS devices: pin to one of them (lowest id,
        deterministic) rather than fall back to the process default
        device — that would dump another group's work onto device 0 and
        break group isolation.  Cached per verifier."""
        if self._pin_sharding is None:
            from jax.sharding import SingleDeviceSharding
            dev = min(sh.device_set, key=lambda d: d.id)
            self._pin_sharding = SingleDeviceSharding(dev)
        return self._pin_sharding

    def _round_sharding(self, pad: int):
        """The placement of a `pad`-lane batch per the cached `_placement`
        (the round axis is this domain's DP/SP axis, SURVEY.md §5.7): the
        multi-device round-axis sharding when the batch splits cleanly,
        else one of this verifier's own devices; None = no placement."""
        sh = self._placement()
        if sh is None:
            return None
        nsh = len(sh.device_set)
        if nsh > 1 and (pad < self.SHARD_MIN_PAD or pad % nsh != 0):
            # tiny/indivisible batches don't split — but they must still
            # run on this verifier's own devices, not the default one
            sh = self._pin_fallback(sh)
        return sh

    def _shard_round_axis(self, enc):
        """Place the round axis of `enc` per `_round_sharding`;
        no-placement runs are unchanged."""
        pad = self._leaf_len(enc)
        sh = self._round_sharding(pad)
        if sh is None:
            return enc

        def put(t):
            return jax.device_put(t, sh) if t.shape[0] == pad else t

        return jax.tree.map(put, enc)

    @staticmethod
    def _leaf_len(enc):
        return jax.tree.leaves(enc)[0].shape[0]

    @staticmethod
    def _norm_enc(enc, front=None):
        """Accept both encoding spellings: the legacy 4-tuple
        (sig_x, sign, u0, u1) — the FIELDS front, still produced by
        `_encode` for external callers (bench config 2,
        `__graft_entry__.entry`) — and the front-aware 3-tuple
        (sig_x, sign, msg)."""
        if len(enc) == 4:
            sig_x, sign, u0, u1 = enc
            return (sig_x, sign, (u0, u1)), FRONT_FIELDS
        return enc, (front or FRONT_FIELDS)

    def _rlc_call(self, enc, n, front=None):
        """(pipeline, args) of one RLC check.  The randomizer bits are
        sampled on device from a fresh 128-bit key; n rides as a 0-d
        operand so every chunk shares one compiled program."""
        import jax.numpy as jnp
        enc, front = self._norm_enc(enc, front)
        sh = self._round_sharding(self._leaf_len(enc))
        sig_x, sign, msg = self._shard_round_axis(enc)
        dst = self.scheme.dst
        if sh is not None and len(sh.device_set) > 1:
            pipe = _rlc_pipeline_sharded(self.g2sig, front, dst, sh.mesh)
        elif self.g2sig:
            pipe = _rlc_pipeline_g2sig(front, dst)
        else:
            pipe = _rlc_pipeline_g1sig(front, dst)
        return pipe, (sig_x, sign, msg, jnp.asarray(_rlc_keys()),
                      jnp.uint32(n), self.pk_aff, self.fixed_aff)

    def _rlc_dispatch(self, enc, n, front=None):
        """Dispatch one RLC check (no sync): returns the device-side fused
        verdict scalar."""
        pipe, args = self._rlc_call(enc, n, front)
        front = self._norm_enc(enc, front)[1]
        _, all_ok = run_program(pipe, *args, name=f"{self._g}_rlc.{front}")
        return all_ok

    def _rlc_ok(self, enc, n, front=None) -> bool:
        """One RLC check over an encoded range; True iff all n rounds verify."""
        return bool(self._rlc_dispatch(enc, n, front=front))

    def _exact(self, enc, n, front=None) -> np.ndarray:
        """Per-round exact pairing checks over an encoded range."""
        enc, front = self._norm_enc(enc, front)
        sig_x, sign, msg = enc
        dst = self.scheme.dst
        pipe = _exact_pipeline_g2sig(front, dst) if self.g2sig \
            else _exact_pipeline_g1sig(front, dst)
        return np.asarray(run_program(pipe, sig_x, sign, msg,
                                      self.pk_aff, self.fixed_aff,
                                      name=f"{self._g}_exact.{front}"))[:n]

    # Below this range size a failed RLC goes straight to exact checks;
    # above it, bisect with RLC halves so one bad round costs O(log n) RLC
    # passes + one small exact pass instead of exact pairings for the whole
    # chunk.  Compiled shapes stay fixed: every RLC level runs at the
    # batch's own width (lanes past n are masked), so bisection compiles
    # no new RLC program — each one is minutes of cold compile on the
    # chip, against a fraction of a second of extra device time per pass —
    # and the exact leaf pads to a power of two <= _BISECT_MIN.
    _BISECT_MIN = 64

    def _verify_range(self, enc, lo, hi, bad, top=False,
                      front=None, failed=False) -> np.ndarray:
        """Verdicts for rounds [lo, hi); `failed` = the RLC over this
        range already came back false (skip re-running it)."""
        n = hi - lo
        # top level: use the batch encoding at its full width (which may
        # exceed _pad_len(n) — the lane_width it was packed at, sharing
        # one compiled program shape across chunks and chains)
        sub = enc if top else self._slice_enc(enc, lo, hi,
                                              self._leaf_len(enc))
        if not failed and not bad[lo:hi].any() \
                and self._rlc_ok(sub, n, front=front):
            return np.ones(n, dtype=bool)
        if n <= self._BISECT_MIN:
            leaf = self._slice_enc(enc, lo, hi)
            return self._exact(leaf, n, front=front) & ~bad[lo:hi]
        mid = lo + n // 2
        return np.concatenate([
            self._verify_range(enc, lo, mid, bad, front=front),
            self._verify_range(enc, mid, hi, bad, front=front),
        ])

    def verify_batch(self, rounds, sigs, prev_sigs=None) -> np.ndarray:
        """Verify N beacons; returns a bool validity array of length N.

        Fast path: one RLC check for the whole batch.  On failure, RLC
        bisection narrows to the bad region, then exact per-round checks
        locate the invalid rounds.  Points and raw messages are encoded
        exactly once; bisection works on slices of that encoding (the
        device fronts re-hash a sliced sub-range inside its dispatch —
        hashing is a few percent of a pairing pass)."""
        n = len(rounds)
        if n == 0:
            return np.zeros(0, dtype=bool)
        if prev_sigs is None:
            prev_sigs = [None] * n
        enc, bad, front = self._pack_enc(rounds, sigs, prev_sigs,
                                         self.lane_width(n))
        return self._verify_range(enc, 0, n, bad, top=True, front=front)

    # -- pack / dispatch / resolve: the double-buffer triple -----------------
    # The verify service's pipelined executor drives these three stages for
    # EVERY caller (host packing of chunk k+1 overlaps device compute of
    # chunk k); verify_stream below rides the same split for store replay.

    def pack_chunk(self, rounds, sigs, prev_sigs=None):
        """Stage 1, host side: numpy wire parse + message packing (raw
        message words above the h2f threshold — NO host hashing — else
        the host hash-to-field oracle).  Returns an opaque packed tuple
        for dispatch/resolve.  Wall time is the `verify.pack` span
        (`pack_seconds()`)."""
        n = len(rounds)
        with metrics.span("verify.pack", round=rounds[0] if n else 0):
            if prev_sigs is None:
                prev_sigs = [None] * n
            enc, bad, front = self._pack_enc(
                rounds, sigs, prev_sigs, self.lane_width(n))
        return (n, enc, bad, front)

    def dispatch_packed(self, packed):
        """Stage 2: enqueue one RLC pass on device (no sync).  Returns the
        device-side fused verdict, or None when malformed slots force the
        exact fallback.  The encoding stays valid, so the verify service's
        failover ladder may dispatch the same packed chunk again."""
        n, enc, bad, front = packed
        if bad.any():
            return None                   # rare: straight to fallback
        return self._rlc_dispatch(enc, n, front=front)

    def resolve_packed(self, packed, verdict) -> np.ndarray:
        """Stage 3: block on the verdict scalar; bisect to the culprits on
        failure.  Returns the per-round validity array."""
        n, enc, bad, front = packed
        if verdict is not None:
            with metrics.span("verify.wait"):
                ok = bool(verdict)
            if ok:
                return np.ones(n, dtype=bool)
        # slow path: bisection + exact checks locate the bad rounds
        return self._verify_range(enc, 0, n, bad, top=True, front=front,
                                  failed=True)

    def pipeline_depth(self, depth=None, chunk_size: int = 8192) -> int:
        """Effective dispatch-pipeline depth: the requested depth (arg >
        DRAND_VERIFY_PIPELINE_DEPTH default), clamped by the per-chunk
        footprint so depth x chunk bytes stays under the in-flight budget
        (depth cannot blow device memory no matter what the knob says)."""
        want = depth if depth is not None else DEFAULT_PIPELINE_DEPTH
        pad = self.lane_width(chunk_size)
        return max(1, min(int(want), max_pipeline_depth(pad, self.g2sig)))

    def verify_stream(self, beacons, chunk_size: int = 8192, depth=None):
        """Streamed verification of an iterable of beacons (BASELINE
        config 5: replay from a populated store).  Host packing of chunk
        i+1 (numpy wire parse + native hash-to-field + transfer) overlaps
        the device pass over chunk i via double buffering — the honest
        end-to-end path for fresh data, unlike re-verifying one resident
        batch.  Yields (rounds, ok ndarray) per chunk.

        `depth` generalizes the r5 double buffer to a depth-k in-flight
        window: up to k chunks stay ENQUEUED ahead of the resolve point,
        so the per-dispatch latency amortizes across k dispatches
        instead of being paid serially (ISSUE 10; clamped by the
        per-chunk footprint via pipeline_depth so VMEM is safe)."""
        from concurrent.futures import ThreadPoolExecutor

        def pack(chunk):
            rounds = [b.round for b in chunk]
            return rounds, self.pack_chunk(rounds,
                                           [b.signature for b in chunk],
                                           [b.previous_sig for b in chunk])

        def chunks():
            buf = []
            for b in beacons:
                buf.append(b)
                if len(buf) == chunk_size:
                    yield buf
                    buf = []
            if buf:
                yield buf

        def dispatch(item):
            rounds, packed = item
            return rounds, packed, self.dispatch_packed(packed)

        def resolve(item):
            rounds, packed, verdict = item
            return rounds, self.resolve_packed(packed, verdict)

        # Two overlapped stages: the pack thread prepares chunk i+1 while
        # the device runs chunk i, and the fused-verdict readback of chunk
        # i-1 happens only after chunk i's program is already enqueued —
        # the blocking device round trip per chunk hides behind the next
        # chunk's device time (r5: the sync in the dispatch path cost one
        # dispatch latency + readback per chunk of pure serial stall).
        from collections import deque
        inflight = deque()
        k = self.pipeline_depth(depth, chunk_size)
        # pack is in-process numpy + native hash-to-field — minutes of
        # silence means the process is wedged, not slow; bound the wait
        pack_timeout = 600.0
        with ThreadPoolExecutor(max_workers=1) as ex:
            pending = None
            for chunk in chunks():
                nxt = ex.submit(pack, chunk)
                if pending is not None:
                    inflight.append(dispatch(pending.result(pack_timeout)))
                    while len(inflight) > k:
                        yield resolve(inflight.popleft())
                pending = nxt
            if pending is not None:
                inflight.append(dispatch(pending.result(pack_timeout)))
            while inflight:
                yield resolve(inflight.popleft())

    def verify_chain(self, beacons):
        """Verify a chained sequence of (round, sig, prev_sig) host-side
        linkage + batched signature verification (SURVEY.md §5.7: hash
        chaining is the cheap serial pass; pairings stay batched).

        Returns (all_ok, per-beacon validity array)."""
        n = len(beacons)
        link_ok = np.ones(n, dtype=bool)
        if self.scheme.chained:
            for i in range(1, n):
                if beacons[i].previous_sig != beacons[i - 1].signature:
                    link_ok[i] = False
        rounds = [b.round for b in beacons]
        sigs = [b.signature for b in beacons]
        prevs = [b.previous_sig for b in beacons]
        sig_ok = self.verify_batch(rounds, sigs, prevs)
        valid = link_ok & sig_ok
        return bool(valid.all()), valid


# ---------------------------------------------------------------------------
# Batched signing (mock networks, perf tests, multi-beacon daemons)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _sign_pipeline(g2sig: bool):
    def run(u0, u1, bits):
        if g2sig:
            hm = DH.hash_to_g2_jac(u0, u1)
            out = DC.G2_DEV.scalar_mul_bits(hm, bits)
            return DC.G2_DEV.to_affine(out)
        hm = DH.hash_to_g1_jac(u0, u1)
        out = DC.G1_DEV.scalar_mul_bits(hm, bits)
        return DC.G1_DEV.to_affine(out)

    return jax.jit(run)


def sign_batch(scheme: Scheme, secret: int, msgs) -> list:
    """BLS-sign many messages with one secret on device; returns sig bytes."""
    n = len(msgs)
    pad = _pad_len(n)
    g2sig = scheme.sig_group is GroupG2
    pmsgs = _pad_msgs(msgs, pad)
    if g2sig:
        u0, u1 = DH.hash_msgs_to_field_g2(pmsgs, scheme.dst)
    else:
        u0, u1 = DH.hash_msgs_to_field_g1(pmsgs, scheme.dst)
    bits = DC.scalars_to_bits([secret] * pad, nbits=256)
    x, y, _ = run_program(_sign_pipeline(g2sig), u0, u1, bits,
                          name=f"{'g2' if g2sig else 'g1'}_sign")
    if g2sig:
        pts = _affine_g2_to_host(x, y)
        return [S.g2_to_bytes(pt) for pt in pts[:n]]
    pts = _affine_g1_to_host(x, y)
    return [S.g1_to_bytes(pt) for pt in pts[:n]]


def _affine_g1_to_host(x, y):
    xs, ys = L.decode_mont(x), L.decode_mont(y)
    if isinstance(xs, int):
        xs, ys = [xs], [ys]
    return list(zip(xs, ys))


def _affine_g2_to_host(x, y):
    x0, x1 = L.decode_mont(x[0]), L.decode_mont(x[1])
    y0, y1 = L.decode_mont(y[0]), L.decode_mont(y[1])
    if isinstance(x0, int):
        x0, x1, y0, y1 = [x0], [x1], [y0], [y1]
    return [((a, b), (c, d)) for a, b, c, d in zip(x0, x1, y0, y1)]


# ---------------------------------------------------------------------------
# Batched tBLS recovery: Lagrange interpolation in the exponent as MSM
# (replaces kyber tbls.Recover at chainstore.go:202 for bulk aggregation)
# ---------------------------------------------------------------------------

def _parse_grid(sig_grid, t: int, nr: int, g2sig: bool):
    """(rounds, t) wire sigs -> (x limb array (t*nr, ...), sign bits,
    bad mask), all pure numpy — the y recovery happens ON DEVICE inside
    the fused recover pipeline (the r4 single-scan sqrt_ratio front end,
    ported here).  Replaces the native-C/host decompression that used to
    run per point before the device ever saw the batch."""
    flat = [bytes(sig_grid[r][j]) for j in range(t) for r in range(nr)]
    return _wire_parse(flat, g2sig)


@lru_cache(maxsize=None)
def _recover_pipeline(g2sig: bool):
    """Fused decompress + Lagrange recovery: the wire x coordinates are
    decompressed on device (ONE shared E2/(p-3)/4 pow scan over all t*nr
    lanes), the Lagrange MSM runs as a signed-digit GLV ladder over the
    psi/phi lanes (66 steps on G2, 130 on G1, vs the old 256-step
    ladder), and the per-round sums + affine conversion ride the same
    program — ONE dispatch per recover batch instead of decompress +
    recover as separate stages."""
    def run(sig_x, sign, bits, neg):
        # sig_x leaves (t, nr, NLIMB); sign (t*nr,);
        # bits (nbits, L*t, nr); neg (L*t, nr) with L = the GLV lane count
        jnp = jax.numpy
        curve = DC.G2_DEV if g2sig else DC.G1_DEV
        if g2sig:
            t, nr = sig_x[0].shape[:2]
            flat2 = lambda a: a.reshape((t * nr,) + a.shape[2:])
            sig_jac, ok = DH.g2_recover_y(flat2(sig_x[0]), flat2(sig_x[1]),
                                          sign)
            lanes = DC.g2_psi_lanes(sig_jac)
        else:
            t, nr = sig_x.shape[:2]
            sig_jac, ok = DH.g1_recover_y(
                sig_x.reshape((t * nr,) + sig_x.shape[2:]), sign)
            lanes = DC.g1_phi_lanes(sig_jac)
        nlanes = bits.shape[1]                # L*t (static)
        base = curve._select(neg.reshape(-1) == 1,
                             curve.neg(lanes), lanes)
        base = jax.tree.map(
            lambda a: a.reshape((nlanes, nr) + a.shape[1:]), base)
        mult = curve.scalar_mul_bits(base, bits)   # (L*t, nr) points
        acc = curve.sum_points(mult)               # reduce axis 0 -> (nr,)
        x, y, _ = curve.to_affine(acc)
        return x, y, jnp.all(ok)

    return jax.jit(run)


def recover_batch(scheme: Scheme, indices, partial_sigs) -> list:
    """Recover full signatures for many rounds at once.

    indices: (rounds, t) signer indices; partial_sigs: (rounds, t) raw BLS sig
    bytes (WITHOUT the 2-byte index prefix).  Assumes partials pre-verified
    (the aggregator feeds only validated partials, chainstore.go:241).
    Returns list of full signature bytes."""
    import jax.numpy as jnp
    nr = len(indices)
    t = len(indices[0])
    g2sig = scheme.sig_group is GroupG2
    # host: Lagrange coefficients (Python ints mod r, t*nr of them), then
    # signed GLV digits so the device ladder is 66/130 steps, not 256
    lams = [HT._lagrange_coeff(indices[r], indices[r][j])
            for j in range(t) for r in range(nr)]
    decompose = DC.glv_decompose_g2 if g2sig else DC.glv_decompose_g1
    nlanes = DC.GLV_G2_LANES if g2sig else DC.GLV_G1_LANES
    nbits = DC.GLV_G2_NBITS if g2sig else DC.GLV_G1_NBITS
    bits, neg = decompose(lams)              # (nbits, L, t*nr), (L, t*nr)
    bits = bits.reshape(nbits, nlanes * t, nr)
    neg = neg.reshape(nlanes * t, nr)
    xw, sgn, bad = _parse_grid(partial_sigs, t, nr, g2sig)
    if bad.any():
        raise ValueError("invalid partial signature encoding")
    if g2sig:
        sig_x = (jnp.asarray(xw[:, 0].reshape(t, nr, L.NLIMB)),
                 jnp.asarray(xw[:, 1].reshape(t, nr, L.NLIMB)))
    else:
        sig_x = jnp.asarray(xw.reshape(t, nr, L.NLIMB))
    x, y, dec_ok = run_program(_recover_pipeline(g2sig), sig_x,
                               jnp.asarray(sgn), bits, neg,
                               name=f"{'g2' if g2sig else 'g1'}_recover")
    if not bool(dec_ok):
        # a wire x with no y on the curve — the host decoder's ValueError,
        # detected on device by the shared sqrt scan instead
        raise ValueError("invalid partial signature encoding")
    if g2sig:
        host_pts = _affine_g2_to_host(x, y)
        return [S.g2_to_bytes(pt) for pt in host_pts]
    host_pts = _affine_g1_to_host(x, y)
    return [S.g1_to_bytes(pt) for pt in host_pts]

