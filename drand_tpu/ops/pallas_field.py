"""Pallas TPU kernels for the BLS12-381 hot loops (pow chains, scalar ladders).

Why this exists (PERF.md): the XLA limb engine is *latency-bound*, not
ALU-bound — every double-and-add ladder step costs ~5 ms of dispatch/schedule
overhead because each step is thousands of tiny HLO ops, while the actual
vector work is microseconds.  The four stages that dominate batched beacon
verification (subgroup-check ladders, hash-to-curve pow chains, the RLC
ladder, cofactor clearing) are all sequential chains of field ops.  Pallas
lets us compile each *whole chain* into ONE kernel: a `lax.fori_loop` whose
body is a full group-law step, with all limb state resident in VMEM/registers.

Layout: inside kernels a field element is a ``(..., 24, B)`` uint32 tensor —
limbs on sublanes, batch on lanes (B a multiple of the 128-lane tile).  This
is the transpose of the XLA engine's ``(..., 24)`` layout; wrappers
transpose/pad at the kernel boundary (cheap XLA reshapes in HBM).

The group-law formulas are NOT re-implemented: `DevCurve` (ops/curve.py) is
generic over a `FieldFns` namespace, so the same tested double/add code runs
inside the kernels over the Pallas field namespace below.

Reference analogue: this file plays the role of the x86-64 assembly in
`kilic/bls12-381` (SURVEY.md §2.9) — the hand-scheduled native backend under
a generic field interface.

Engine selection (`DRAND_TPU_PALLAS`, read by `enabled()`): it chooses the
LANE-MAJOR engine of this module over the XLA limb engine — `auto`
(default) on a TPU backend only, `1`/`interp` on every backend (the two
spellings are the same setting), `0` never.  It does not choose how the
lane-major chains lower: that is `_use_kernels()`, true exactly on a TPU
backend, where each chain runs as its compiled Pallas kernel.  Elsewhere
the IDENTICAL chain math (`_pow_math`/`_ladder_*_math`) runs as plain
jitted XLA — that is what the CPU test suite covers, plus the
operand/layout wrappers shared by both lowerings.  No setting runs the
Pallas interpreter.  `engine_mode()` names the combination in force.
"""

import math
import os
import threading
from contextlib import contextmanager
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import limbs as L
from .curve import DevCurve, FieldFns
from ..crypto.host.params import P as FP_P, B1, B2

NL = L.NLIMB          # 24 limbs of 16 bits
MASK = L.MASK
U32 = L.U32

# Lane-layout constants: (24, 1) columns broadcasting over the lane axis.
# NUMPY on purpose: this module is imported lazily, possibly inside an active
# jit trace — jnp constants created there would be tracers and leak across
# traces.  numpy arrays convert at each use site instead.
_P_LANE = np.asarray(L.int_to_limbs(FP_P))[:, None]
_ONE_LANE = np.asarray(L.int_to_limbs(L.R_MONT))[:, None]
_N0 = np.uint32(L.N0)

TILE = int(os.environ.get("DRAND_TPU_PALLAS_TILE", "256"))

# Pallas kernels may not close over array constants — field constants enter
# each kernel as operands (a stacked (K, 24, tile) bundle for the pairing
# kernels; (24, TILE) p/one pair for the chain kernels), installed for the
# trace via this context.  Outside any kernel the numpy fallbacks apply.
# Per THREAD: the verify service, several daemons in one process and
# chip_smoke's warm-up pool trace kernels concurrently, and a context one
# thread installs must never leak into (or vanish from) another's trace.
_TLS = threading.local()


def _ctx() -> dict:
    ctx = getattr(_TLS, "ctx", None)
    if ctx is None:
        ctx = _TLS.ctx = {}
    return ctx


def _mont_np(x: int) -> np.ndarray:
    return np.asarray(L.int_to_limbs(x * L.R_MONT % FP_P))


def _const_entries():
    from ..crypto.host import field as HFhost
    from ..crypto.host.params import B2
    ents = [("p", np.asarray(L.int_to_limbs(FP_P))),
            ("one", _mont_np(1)),
            ("half", _mont_np((FP_P + 1) // 2)),
            ("beta", _mont_np(pow(2, (FP_P - 1) // 3, FP_P))),
            ("b2_0", _mont_np(B2[0])), ("b2_1", _mont_np(B2[1]))]
    for j in (1, 2):
        for i, c in enumerate(HFhost._FROB[j]):
            ents.append((f"frob{j}_{i}_0", _mont_np(c[0])))
            ents.append((f"frob{j}_{i}_1", _mont_np(c[1])))
    return ents


_CONST_ENTRIES = _const_entries()
_CONST_IDX = {name: i for i, (name, _) in enumerate(_CONST_ENTRIES)}
_CONST_STACK = np.stack([v for _, v in _CONST_ENTRIES])       # (K, 24)
NCONST = len(_CONST_ENTRIES)


def _c(name: str):
    """Named field constant in the active layout/context."""
    ctx = _ctx()
    if "consts" in ctx:
        return ctx["consts"][_CONST_IDX[name]]
    if name in ctx:
        return ctx[name]
    return _CONST_STACK[_CONST_IDX[name]][:, None]            # numpy (24, 1)


def _p_lane():
    return _c("p")


def _one_lane():
    return _c("one")


@contextmanager
def _kernel_consts(**kw):
    ctx = _ctx()
    old = dict(ctx)
    ctx.update(kw)
    try:
        yield
    finally:
        ctx.clear()
        ctx.update(old)


_P_FULL = np.ascontiguousarray(np.broadcast_to(_P_LANE, (NL, TILE)))
_ONE_FULL = np.ascontiguousarray(np.broadcast_to(_ONE_LANE, (NL, TILE)))


@lru_cache(maxsize=None)
def _const_bundle(tile: int) -> np.ndarray:
    return np.ascontiguousarray(
        np.broadcast_to(_CONST_STACK[:, :, None], (NCONST, NL, tile)))


def enabled() -> bool:
    """True when the lane-major engine replaces the XLA limb engine (see
    the module docstring: `1`/`interp` do not mean the interpreter)."""
    mode = os.environ.get("DRAND_TPU_PALLAS", "auto")
    if mode == "0":
        return False
    if mode in ("1", "interp"):
        return True
    if mode == "auto":
        return jax.default_backend() == "tpu"
    return False


# ---------------------------------------------------------------------------
# Field ops on the lane-major layout (..., 24, B).  Pure jnp — usable both
# inside Pallas kernels and (for tests) as plain XLA ops.
# ---------------------------------------------------------------------------


def _shift_up(x, k=1):
    """Move limb i to limb i+k (multiply by 2^(16k)); zeros shift in."""
    z = jnp.zeros(x.shape[:-2] + (k,) + x.shape[-1:], x.dtype)
    return jnp.concatenate([z, x[..., :-k, :]], axis=-2)


def _norm(cols, nout: int):
    """Exact base-2^16 limbs of sum(cols_i · 2^16i) mod 2^(16·nout).

    cols: (..., m, B) uint32 columns, each < 2^23.  Three vector relax
    passes bound every column by 2^16, then an unrolled Kogge-Stone
    generate/propagate pass resolves the remaining single-bit ripple —
    no O(limbs) sequential scan (which would serialize on the sublane axis).
    """
    m = cols.shape[-2]
    if m < nout:
        z = jnp.zeros(cols.shape[:-2] + (nout - m,) + cols.shape[-1:], U32)
        cols = jnp.concatenate([cols, z], axis=-2)
    elif m > nout:
        raise ValueError("cols wider than nout")
    c = cols
    for _ in range(3):
        c = (c & MASK) + _shift_up(c >> 16)
    # now every column <= 2^16: single-bit carries remain
    g = c >> 16                       # generate (c == 2^16)
    p_ = (c == MASK).astype(U32)      # propagate
    d = 1
    while d < nout:
        g = g | (p_ & _shift_up(g, d))
        p_ = p_ & _shift_up(p_, d)
        d *= 2
    return (c + _shift_up(g, 1)) & MASK


def _cond_sub_p(a):
    """a < 2p (24 limbs) -> canonical a mod p."""
    diff, borrow = _sub_raw(a)
    return jnp.where((borrow == 0)[..., None, :], diff, a)


def _embed(x, start: int, total: int):
    """Place x's rows at [start, start+rows) within `total` rows (axis -2).

    Concatenation with zeros instead of scattered updates: Mosaic has no
    scatter-add, and a static-offset embed lowers to cheap sublane concats."""
    rows = x.shape[-2]
    parts = []
    if start:
        parts.append(jnp.zeros(x.shape[:-2] + (start,) + x.shape[-1:], x.dtype))
    parts.append(x)
    tail = total - start - rows
    if tail:
        parts.append(jnp.zeros(x.shape[:-2] + (tail,) + x.shape[-1:], x.dtype))
    return jnp.concatenate(parts, axis=-2) if len(parts) > 1 else x


def _sub_raw(a, b=None):
    """a - (b or p) over 24 limbs; returns (diff mod 2^384, borrow in {0,1})."""
    bb = _p_lane() if b is None else b
    v = a + (MASK - bb)                       # each in [0, 2^17-2]
    v = jnp.concatenate([v[..., 0:1, :] + 1, v[..., 1:, :]], axis=-2)  # +1
    d = _norm(v, NL + 1)
    carry = d[..., NL, :]
    return d[..., :NL, :], 1 - carry


def pf_add(a, b):
    s = _norm(a + b, NL + 1)
    limbs, carry = s[..., :NL, :], s[..., NL, :]
    diff, borrow = _sub_raw(limbs)
    take = ((carry == 1) | (borrow == 0))[..., None, :]
    return jnp.where(take, diff, limbs)


def pf_sub(a, b):
    d, borrow = _sub_raw(a, b)
    fixed = _norm(d + _p_lane(), NL)
    return jnp.where((borrow == 1)[..., None, :], fixed, d)


def pf_neg(a):
    d, _ = _sub_raw(jnp.broadcast_to(_p_lane(), a.shape), a)
    return jnp.where(pf_is_zero(a)[..., None, :], a, d)


def _lohi25(prod):
    """Split a (..., 24, B) product row-block into its 25-row lo+hi columns."""
    z1 = jnp.zeros(prod.shape[:-2] + (1,) + prod.shape[-1:], U32)
    lo = jnp.concatenate([prod & MASK, z1], axis=-2)
    hi = jnp.concatenate([z1, prod >> 16], axis=-2)
    return lo + hi


def pf_mul(a, b):
    """CIOS-fused Montgomery multiply: one pass interleaves the operand
    product and the word-wise reduction, so each of the 24 iterations does
    a single full-width accumulate (t += lohi(a_i·b) + lohi(m_i·p))
    instead of conv and REDC each doing their own — the wide adds, not the
    multiplies, dominate the kernel's VPU traffic.

    Bounds: a lohi25 column is < 2^17; two of them per iteration over 24
    iterations keeps every column < 24·2^18 < 2^23 — no uint32 overflow.
    m_i = (t_i + low16(a_i·b_0))·n0' mod 2^16 uses uint32 wrap (2^16 | 2^32
    keeps the low half exact), exactly as the split _redc did."""
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape)
    b = jnp.broadcast_to(b, shape)
    p = _p_lane()
    t = jnp.zeros(shape[:-2] + (2 * NL, shape[-1]), U32)
    for i in range(NL):
        prod = a[..., i:i + 1, :] * b        # exact uint32 (16x16-bit)
        ti = t[..., i:i + 1, :] + (prod[..., 0:1, :] & MASK)
        m = (ti * _N0) & MASK
        addend = _lohi25(prod) + _lohi25(m * p)
        t = t + _embed(addend, i, 2 * NL)
        carry = t[..., i:i + 1, :] >> 16
        t = jnp.concatenate(
            [t[..., :i + 1, :], t[..., i + 1:i + 2, :] + carry,
             t[..., i + 2:, :]], axis=-2)
    return _cond_sub_p(_norm(t[..., NL:, :], NL))


def pf_sqr(a):
    return pf_mul(a, a)


def pf_is_zero(a):
    return jnp.all(a == 0, axis=-2)


def pf_eq(a, b):
    return jnp.all(a == b, axis=-2)


def pf_select(cond, a, b):
    return jnp.where(cond[..., None, :], a, b)


def pf_zeros(shape=()):
    return jnp.zeros((NL,) + shape, U32)


def pf_ones(shape=()):
    one = _one_lane()
    return jnp.broadcast_to(one if shape else one[:, 0], (NL,) + shape)


def _stack(xs):
    shape = jnp.broadcast_shapes(*[x.shape for x in xs])
    return jnp.stack([jnp.broadcast_to(x, shape) for x in xs], axis=0)


def pf_mul_many(pairs):
    if len(pairs) == 1:
        return (pf_mul(pairs[0][0], pairs[0][1]),)
    out = pf_mul(_stack([p[0] for p in pairs]), _stack([p[1] for p in pairs]))
    return tuple(out[i] for i in range(len(pairs)))


def pf_add_many(pairs):
    if len(pairs) == 1:
        return (pf_add(pairs[0][0], pairs[0][1]),)
    out = pf_add(_stack([p[0] for p in pairs]), _stack([p[1] for p in pairs]))
    return tuple(out[i] for i in range(len(pairs)))


def pf_sub_many(pairs):
    if len(pairs) == 1:
        return (pf_sub(pairs[0][0], pairs[0][1]),)
    out = pf_sub(_stack([p[0] for p in pairs]), _stack([p[1] for p in pairs]))
    return tuple(out[i] for i in range(len(pairs)))


def _no_inv(a):  # pragma: no cover - kernels never invert
    raise NotImplementedError("no inversion inside Pallas kernels")


# ---------------------------------------------------------------------------
# Fp2 on the lane layout (tower.py formulas over the pf ops)
# ---------------------------------------------------------------------------


def pf2_add(a, b):
    r = pf_add_many([(a[0], b[0]), (a[1], b[1])])
    return (r[0], r[1])


def pf2_sub(a, b):
    r = pf_sub_many([(a[0], b[0]), (a[1], b[1])])
    return (r[0], r[1])


def pf2_neg(a):
    return (pf_neg(a[0]), pf_neg(a[1]))


def pf2_mul_many(pairs):
    k = len(pairs)
    sums = pf_add_many([(a[0], a[1]) for a, _ in pairs]
                       + [(b[0], b[1]) for _, b in pairs])
    t = pf_mul_many(
        [(a[0], b[0]) for a, b in pairs]
        + [(a[1], b[1]) for a, b in pairs]
        + [(sums[i], sums[k + i]) for i in range(k)])
    t0, t1, t2 = t[:k], t[k:2 * k], t[2 * k:]
    s = pf_sub_many([(t0[i], t1[i]) for i in range(k)]
                    + [(t2[i], t0[i]) for i in range(k)])
    c0, u = s[:k], s[k:]
    c1 = pf_sub_many([(u[i], t1[i]) for i in range(k)])
    return [(c0[i], c1[i]) for i in range(k)]


def pf2_mul(a, b):
    return pf2_mul_many([(a, b)])[0]


def pf2_sqr_many(xs):
    k = len(xs)
    sums = pf_add_many([(a[0], a[1]) for a in xs])
    difs = pf_sub_many([(a[0], a[1]) for a in xs])
    t = pf_mul_many([(sums[i], difs[i]) for i in range(k)]
                    + [(a[0], a[1]) for a in xs])
    c1 = pf_add_many([(t[k + i], t[k + i]) for i in range(k)])
    return [(t[i], c1[i]) for i in range(k)]


def pf2_sqr(a):
    return pf2_sqr_many([a])[0]


def pf2_is_zero(a):
    return pf_is_zero(a[0]) & pf_is_zero(a[1])


def pf2_eq(a, b):
    return pf_eq(a[0], b[0]) & pf_eq(a[1], b[1])


def pf2_select(cond, a, b):
    return (pf_select(cond, a[0], b[0]), pf_select(cond, a[1], b[1]))


def pf2_zeros(shape=()):
    z = pf_zeros(shape)
    return (z, z)


def pf2_ones(shape=()):
    return (pf_ones(shape), pf_zeros(shape))


_lane_batch_shape = lambda leaf: leaf.shape[-1:]

PF_FP = FieldFns(
    add=pf_add, sub=pf_sub, mul=pf_mul, mul_many=pf_mul_many,
    sqr=pf_sqr, neg=pf_neg, inv=_no_inv, is_zero=pf_is_zero, eq=pf_eq,
    select=pf_select, zeros=pf_zeros, ones=pf_ones,
    batch_shape=_lane_batch_shape,
)

PF_FP2 = FieldFns(
    add=pf2_add, sub=pf2_sub, mul=pf2_mul, mul_many=pf2_mul_many,
    sqr=pf2_sqr, neg=pf2_neg, inv=_no_inv, is_zero=pf2_is_zero, eq=pf2_eq,
    select=pf2_select, zeros=pf2_zeros, ones=pf2_ones,
    batch_shape=_lane_batch_shape,
)


def _lane_const(x: int):
    # numpy, not jnp: see the module-constant note above (lazy import under
    # an active trace must not mint tracers)
    return np.asarray(L.int_to_limbs(x * L.R_MONT % FP_P))[:, None]


G1_PF = DevCurve(PF_FP, _lane_const(B1), "G1pf")
G2_PF = DevCurve(PF_FP2, (_lane_const(B2[0]), _lane_const(B2[1])), "G2pf")


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

_COND_OK = os.environ.get("DRAND_TPU_PALLAS_COND", "1") == "1"


def _maybe_cond(bit, then_fn, acc):
    """Skip work when a shared (SMEM) bit is 0.  `lax.cond` on a scalar is
    the fast path; flip DRAND_TPU_PALLAS_COND=0 if a Mosaic version regresses
    on conditionals with big vector carries."""
    if _COND_OK:
        return jax.lax.cond(bit == 1, then_fn, lambda a: a, acc)
    out = then_fn(acc)
    return jax.tree.map(lambda x, y: jnp.where(bit == 1, x, y), out, acc)


def _exp_bits_np(e: int) -> np.ndarray:
    # int32 view of limbs._exp_bits (SMEM scalar operands are int32)
    return np.asarray(L._exp_bits(e), np.int32)


# ---------------------------------------------------------------------------
# Shared chain math (used by BOTH the compiled Pallas kernels on TPU and the
# plain-XLA "direct" fallback on other backends — one body, two lowerings, so
# the CPU test suite covers exactly the math the chip runs).
# ---------------------------------------------------------------------------


def _pow_math(getbit, x, nbits: int):
    acc0 = pf_ones((x.shape[-1],))

    def step(i, acc):
        acc = pf_sqr(acc)
        return _maybe_cond(getbit(i), lambda a: pf_mul(a, x), acc)

    return jax.lax.fori_loop(0, nbits, step, acc0)


def _ladder_var_math(kind: str, getrow, pt, nbits: int):
    curve = _curve_of(kind)
    acc0 = curve.infinity((_flat_point(pt)[0].shape[-1],))

    def step(i, acc):
        acc = curve.double(acc)
        added = curve.add(acc, pt)
        cond = getrow(i) == 1                              # (1, B)
        return jax.tree.map(lambda x, y: jnp.where(cond, x, y), added, acc)

    return jax.lax.fori_loop(0, nbits, step, acc0)


def _ladder_fixed_math(kind: str, getbit, pt, nbits: int):
    curve = _curve_of(kind)
    acc0 = curve.infinity((_flat_point(pt)[0].shape[-1],))

    def step(i, acc):
        acc = curve.double(acc)
        return _maybe_cond(getbit(i), lambda a: curve.add(a, pt), acc)

    return jax.lax.fori_loop(0, nbits, step, acc0)


def _curve_of(kind: str):
    return G1_PF if kind == "G1" else G2_PF


def _ncoord(kind: str) -> int:
    return 3 if kind == "G1" else 6


def _pack_point(kind, arrs):
    if kind == "G1":
        return tuple(arrs)
    return ((arrs[0], arrs[1]), (arrs[2], arrs[3]), (arrs[4], arrs[5]))


def _flat_point(p):
    return [x for coord in p
            for x in (coord if isinstance(coord, tuple) else (coord,))]


def _use_kernels() -> bool:
    return jax.default_backend() == "tpu"


def engine_mode() -> str:
    """Which engine the field chains run on in this process:
    "pallas-kernels" (compiled Mosaic kernels, TPU only), "lane-major-xla"
    (the same chain math as jitted XLA) or "xla-limbs"."""
    if not enabled():
        return "xla-limbs"
    return "pallas-kernels" if _use_kernels() else "lane-major-xla"


# ---------------------------------------------------------------------------
# Compiled Pallas kernels (TPU)
# ---------------------------------------------------------------------------

_CONST_SPEC = pl.BlockSpec((NL, TILE), lambda i, *_: (0, 0))
_DATA_SPEC = pl.BlockSpec((NL, TILE), lambda i, *_: (0, i))


@lru_cache(maxsize=None)
def _pow_call(e: int, btot: int):
    nbits = max(e.bit_length(), 1)

    def kernel(bits_ref, p_ref, one_ref, x_ref, o_ref):
        with _kernel_consts(p=p_ref[:, 0:1], one=one_ref[:, 0:1]):
            o_ref[:] = _pow_math(lambda i: bits_ref[i], x_ref[:], nbits)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(btot // TILE,),
        in_specs=[_CONST_SPEC, _CONST_SPEC, _DATA_SPEC],
        out_specs=_DATA_SPEC,
    )
    return pl.pallas_call(
        kernel, grid_spec=gs, name="fp_pow_kernel",
        out_shape=jax.ShapeDtypeStruct((NL, btot), U32))


@lru_cache(maxsize=None)
def _pow_direct(e: int):
    nbits = max(e.bit_length(), 1)

    @jax.jit
    def run(bits, x):
        return _pow_math(lambda i: bits[i], x, nbits)

    return run


def _pow2_math(getbit, x, nbits: int):
    acc0 = pf2_ones((x[0].shape[-1],))

    def step(i, acc):
        acc = pf2_sqr(acc)
        return _maybe_cond(getbit(i), lambda a: pf2_mul(a, x), acc)

    return jax.lax.fori_loop(0, nbits, step, acc0)


@lru_cache(maxsize=None)
def _pow2_call(e: int, btot: int):
    nbits = max(e.bit_length(), 1)

    def kernel(bits_ref, p_ref, one_ref, x0_ref, x1_ref, o0_ref, o1_ref):
        with _kernel_consts(p=p_ref[:, 0:1], one=one_ref[:, 0:1]):
            r = _pow2_math(lambda i: bits_ref[i], (x0_ref[:], x1_ref[:]),
                           nbits)
            o0_ref[:] = r[0]
            o1_ref[:] = r[1]

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(btot // TILE,),
        in_specs=[_CONST_SPEC, _CONST_SPEC, _DATA_SPEC, _DATA_SPEC],
        out_specs=[_DATA_SPEC, _DATA_SPEC],
    )
    return pl.pallas_call(
        kernel, grid_spec=gs, name="fp2_pow_kernel",
        out_shape=[jax.ShapeDtypeStruct((NL, btot), U32)] * 2)


@lru_cache(maxsize=None)
def _pow2_direct(e: int):
    nbits = max(e.bit_length(), 1)

    @jax.jit
    def run(bits, x0, x1):
        return _pow2_math(lambda i: bits[i], (x0, x1), nbits)

    return run


@lru_cache(maxsize=None)
def _ladder_var_call(kind: str, nbits: int, btot: int):
    nc = _ncoord(kind)

    def kernel(p_ref, one_ref, *refs):
        with _kernel_consts(p=p_ref[:, 0:1], one=one_ref[:, 0:1]):
            ins, bits_ref, outs = refs[:nc], refs[nc], refs[nc + 1:]
            pt = _pack_point(kind, [r[:] for r in ins])
            acc = _ladder_var_math(
                kind, lambda i: bits_ref[pl.ds(i, 1), :], pt, nbits)
            for o, v in zip(outs, _flat_point(acc)):
                o[:] = v

    spec = pl.BlockSpec((NL, TILE), lambda i: (0, i))
    gs = pl.GridSpec(
        grid=(btot // TILE,),
        in_specs=[pl.BlockSpec((NL, TILE), lambda i: (0, 0))] * 2
        + [spec] * nc + [pl.BlockSpec((nbits, TILE), lambda i: (0, i))],
        out_specs=[spec] * nc,
    )
    return pl.pallas_call(
        kernel, grid_spec=gs, name=f"{kind.lower()}_ladder_kernel",
        out_shape=[jax.ShapeDtypeStruct((NL, btot), U32)] * nc)


@lru_cache(maxsize=None)
def _ladder_var_direct(kind: str, nbits: int):
    nc = _ncoord(kind)

    @jax.jit
    def run(bits, *arrs):
        pt = _pack_point(kind, list(arrs[:nc]))
        acc = _ladder_var_math(
            kind, lambda i: jax.lax.dynamic_slice_in_dim(bits, i, 1, 0),
            pt, nbits)
        return tuple(_flat_point(acc))

    return run


@lru_cache(maxsize=None)
def _ladder_fixed_call(kind: str, k: int, btot: int):
    nc = _ncoord(kind)
    nbits = max(k.bit_length(), 1)

    def kernel(bits_ref, p_ref, one_ref, *refs):
        with _kernel_consts(p=p_ref[:, 0:1], one=one_ref[:, 0:1]):
            ins, outs = refs[:nc], refs[nc:]
            pt = _pack_point(kind, [r[:] for r in ins])
            acc = _ladder_fixed_math(kind, lambda i: bits_ref[i], pt, nbits)
            for o, v in zip(outs, _flat_point(acc)):
                o[:] = v

    spec = pl.BlockSpec((NL, TILE), lambda i, b: (0, i))
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(btot // TILE,),
        in_specs=[_CONST_SPEC, _CONST_SPEC] + [spec] * nc,
        out_specs=[spec] * nc,
    )
    return pl.pallas_call(
        kernel, grid_spec=gs, name=f"{kind.lower()}_ladder_fixed_kernel",
        out_shape=[jax.ShapeDtypeStruct((NL, btot), U32)] * nc)


@lru_cache(maxsize=None)
def _ladder_fixed_direct(kind: str, k: int):
    nc = _ncoord(kind)
    nbits = max(k.bit_length(), 1)

    @jax.jit
    def run(bits, *arrs):
        pt = _pack_point(kind, list(arrs[:nc]))
        acc = _ladder_fixed_math(kind, lambda i: bits[i], pt, nbits)
        return tuple(_flat_point(acc))

    return run


# ---------------------------------------------------------------------------
# Layout wrappers (drop-in public API)
# ---------------------------------------------------------------------------


def _to_lanes(a, tile: int = TILE):
    """(..., 24) -> ((24, Bpad), batch_shape, B)."""
    shape = a.shape[:-1]
    b = int(np.prod(shape)) if shape else 1
    x = a.reshape(b, NL).T
    bp = max(tile, math.ceil(b / tile) * tile)
    if bp != b:
        x = jnp.pad(x, ((0, 0), (0, bp - b)))
    return x, shape, b


def _from_lanes(x, shape, b):
    return x[:, :b].T.reshape(shape + (NL,))


def pow_fixed(a, e: int):
    """Drop-in for limbs.pow_fixed: whole square-and-multiply chain as one
    Pallas kernel (zero bits skip their multiply via scalar `cond`)."""
    x, shape, b = _to_lanes(a)
    bits = jnp.asarray(_exp_bits_np(e))
    if _use_kernels():
        out = _pow_call(e, x.shape[1])(bits, _P_FULL, _ONE_FULL, x)
    else:
        out = _pow_direct(e)(bits, x)
    return _from_lanes(out, shape, b)


def pow_fixed_fp2(a, e: int):
    """Drop-in for tower.fp2_pow_fixed: the whole Fp2 square-and-multiply
    chain as one Pallas kernel (the G2 sqrt_ratio scan)."""
    x0, shape, b = _to_lanes(a[0])
    x1, _, _ = _to_lanes(a[1])
    bits = jnp.asarray(_exp_bits_np(e))
    if _use_kernels():
        out = _pow2_call(e, x0.shape[1])(bits, _P_FULL, _ONE_FULL, x0, x1)
    else:
        out = _pow2_direct(e)(bits, x0, x1)
    return (_from_lanes(out[0], shape, b), _from_lanes(out[1], shape, b))


def _point_to_lanes(p):
    flat = _flat_point(p)
    shape = flat[0].shape[:-1]
    outs = [_to_lanes(x)[0] for x in flat]
    b = int(np.prod(shape)) if shape else 1
    return outs, shape, b


def _point_from_lanes(kind, arrs, shape, b):
    coords = [_from_lanes(x, shape, b) for x in arrs]
    return _pack_point(kind, coords)


def scalar_mul_bits(kind: str, p, bits):
    """Drop-in for DevCurve.scalar_mul_bits (variable per-element scalars):
    the whole MSB-first double-and-add ladder runs as one Pallas kernel."""
    arrs, shape, b = _point_to_lanes(p)
    nbits = bits.shape[0]
    btot = arrs[0].shape[1]
    bt = bits.reshape(nbits, b).astype(U32)
    if btot != b:
        bt = jnp.pad(bt, ((0, 0), (0, btot - b)))
    if _use_kernels():
        out = _ladder_var_call(kind, nbits, btot)(_P_FULL, _ONE_FULL, *arrs, bt)
    else:
        out = _ladder_var_direct(kind, nbits)(bt, *arrs)
    return _point_from_lanes(kind, out, shape, b)


def scalar_mul_fixed(kind: str, p, k: int):
    """Drop-in for DevCurve.scalar_mul_fixed (static scalar: cofactors, |x|
    chains).  Zero bits skip their group add entirely (scalar `cond`), so an
    |x| ladder costs 64 doubles + hw(|x|)=6 adds."""
    from . import curve as DC
    xla_curve = DC.G1_DEV if kind == "G1" else DC.G2_DEV
    assert k != 0, "k == 0 is handled by DevCurve.scalar_mul_fixed"
    neg = k < 0
    k = abs(k)
    arrs, shape, b = _point_to_lanes(p)
    btot = arrs[0].shape[1]
    bits = jnp.asarray(_exp_bits_np(k))
    if _use_kernels():
        out = _ladder_fixed_call(kind, k, btot)(bits, _P_FULL, _ONE_FULL, *arrs)
    else:
        out = _ladder_fixed_direct(kind, k)(bits, *arrs)
    res = _point_from_lanes(kind, out, shape, b)
    return xla_curve.neg(res) if neg else res


# ---------------------------------------------------------------------------
# Fp6 / Fp12 tower on the lane layout (formulas mirror ops/tower.py, which is
# itself pinned to the host golden code and LoE mainnet vectors).
#
# Deliberate duplication: unlike the group law (shared via FieldFns/DevCurve),
# the tower formulas live in both engines; tower.py is hard-wired to the XLA
# limb namespace.  The bit-exact equivalence suite (test_ops_pallas*.py)
# pins the two engines to each other — a one-sided formula edit fails there.
# ---------------------------------------------------------------------------


def pf2_mul_fp(a, k):
    r = pf_mul_many([(a[0], k), (a[1], k)])
    return (r[0], r[1])


def pf2_conj(a):
    return (a[0], pf_neg(a[1]))


def pf2_mul_xi(a):
    return (pf_sub(a[0], a[1]), pf_add(a[0], a[1]))


def pf2_inv(a):
    """1/a via one Fermat pow chain on the norm (getbit from the context —
    the exponent p-2 enters kernels as a scalar-prefetch bit array)."""
    t = pf_mul_many([(a[0], a[0]), (a[1], a[1])])
    norm = pf_add(t[0], t[1])
    ninv = _pow_math(_ctx()["invbit"], norm, INV_NBITS)
    r = pf_mul_many([(a[0], ninv), (a[1], ninv)])
    return (r[0], pf_neg(r[1]))


INV_NBITS = (FP_P - 2).bit_length()
_INV_BITS_NP = None  # built lazily


def _inv_bits():
    global _INV_BITS_NP
    if _INV_BITS_NP is None:
        _INV_BITS_NP = _exp_bits_np(FP_P - 2)
    return _INV_BITS_NP


def pf6_add(a, b):
    r = pf_add_many([(x[0], y[0]) for x, y in zip(a, b)]
                    + [(x[1], y[1]) for x, y in zip(a, b)])
    return tuple((r[i], r[3 + i]) for i in range(3))


def pf6_sub(a, b):
    r = pf_sub_many([(x[0], y[0]) for x, y in zip(a, b)]
                    + [(x[1], y[1]) for x, y in zip(a, b)])
    return tuple((r[i], r[3 + i]) for i in range(3))


def pf6_neg(a):
    return tuple(pf2_neg(x) for x in a)


def pf6_mul_many(pairs):
    """k Fp6 products, Karatsuba-3: 6k Fp2 products in one pf2_mul_many."""
    k = len(pairs)
    pre = pf_add_many(
        [pr for a, b in pairs for pr in (
            (a[1][0], a[2][0]), (a[1][1], a[2][1]),
            (b[1][0], b[2][0]), (b[1][1], b[2][1]),
            (a[0][0], a[1][0]), (a[0][1], a[1][1]),
            (b[0][0], b[1][0]), (b[0][1], b[1][1]),
            (a[0][0], a[2][0]), (a[0][1], a[2][1]),
            (b[0][0], b[2][0]), (b[0][1], b[2][1]),
        )])
    prods = []
    for i, (a, b) in enumerate(pairs):
        o = i * 12
        prods += [(a[0], b[0]), (a[1], b[1]), (a[2], b[2]),
                  ((pre[o + 0], pre[o + 1]), (pre[o + 2], pre[o + 3])),
                  ((pre[o + 4], pre[o + 5]), (pre[o + 6], pre[o + 7])),
                  ((pre[o + 8], pre[o + 9]), (pre[o + 10], pre[o + 11]))]
    t = pf2_mul_many(prods)
    out = []
    for i in range(k):
        t0, t1, t2, tc12, tc01, tc02 = t[6 * i:6 * i + 6]
        c0 = pf2_add(t0, pf2_mul_xi(pf2_sub(pf2_sub(tc12, t1), t2)))
        c1 = pf2_add(pf2_sub(pf2_sub(tc01, t0), t1), pf2_mul_xi(t2))
        c2 = pf2_add(pf2_sub(pf2_sub(tc02, t0), t2), t1)
        out.append((c0, c1, c2))
    return out


def pf6_mul(a, b):
    return pf6_mul_many([(a, b)])[0]


def pf6_mul_by_v(a):
    return (pf2_mul_xi(a[2]), a[0], a[1])


def pf6_inv(a):
    a0, a1, a2 = a
    t = pf2_mul_many([(a0, a0), (a1, a2), (a2, a2), (a0, a1), (a1, a1), (a0, a2)])
    sq0, m12, sq2, m01, sq1, m02 = t
    c0 = pf2_sub(sq0, pf2_mul_xi(m12))
    c1 = pf2_sub(pf2_mul_xi(sq2), m01)
    c2 = pf2_sub(sq1, m02)
    u = pf2_mul_many([(a1, c2), (a2, c1), (a0, c0)])
    tt = pf2_add(pf2_mul_xi(pf2_add(u[0], u[1])), u[2])
    tinv = pf2_inv(tt)
    r = pf2_mul_many([(c0, tinv), (c1, tinv), (c2, tinv)])
    return (r[0], r[1], r[2])


def pf6_zeros(shape=()):
    z = pf2_zeros(shape)
    return (z, z, z)


def pf6_ones(shape=()):
    return (pf2_ones(shape), pf2_zeros(shape), pf2_zeros(shape))


def pf12_ones(shape=()):
    return (pf6_ones(shape), pf6_zeros(shape))


def pf12_mul(a, b):
    a0, a1 = a
    b0, b1 = b
    t = pf6_mul_many([(a0, b0), (a1, b1), (pf6_add(a0, a1), pf6_add(b0, b1))])
    t0, t1, t2 = t
    return (pf6_add(t0, pf6_mul_by_v(t1)), pf6_sub(pf6_sub(t2, t0), t1))


def pf12_sqr(a):
    a0, a1 = a
    t = pf6_mul_many([(a0, a1), (pf6_add(a0, a1), pf6_add(a0, pf6_mul_by_v(a1)))])
    tt, c0 = t
    c0 = pf6_sub(pf6_sub(c0, tt), pf6_mul_by_v(tt))
    return (c0, pf6_add(tt, tt))


def pf12_conj(a):
    return (a[0], pf6_neg(a[1]))


def pf12_inv(a):
    a0, a1 = a
    t = pf6_mul_many([(a0, a0), (a1, a1)])
    tt = pf6_sub(t[0], pf6_mul_by_v(t[1]))
    tinv = pf6_inv(tt)
    r = pf6_mul_many([(a0, tinv), (a1, tinv)])
    return (r[0], pf6_neg(r[1]))


def pf12_frobenius(a, j: int):
    (c0, c2, c4), (c1, c3, c5) = a
    cs = [c0, c1, c2, c3, c4, c5]
    if j & 1:
        cs = [pf2_conj(c) for c in cs]
    out = pf2_mul_many([(c, (_c(f"frob{j}_{i}_0"), _c(f"frob{j}_{i}_1")))
                        for i, c in enumerate(cs)])
    return ((out[0], out[2], out[4]), (out[1], out[3], out[5]))


# ---------------------------------------------------------------------------
# Pairing: projective Miller loop + final exponentiation (mirrors
# ops/pairing.py step-for-step; replaces the last latency-bound XLA chains
# of the verification pipeline — at RLC batch the pairing runs on 2 lanes,
# pure latency, so the fused kernels win ~100x there)
# ---------------------------------------------------------------------------

from ..crypto.host.params import X as _BLS_X

_XLOOP_BITS_NP = np.array([int(bch) for bch in bin(-_BLS_X)[3:]], dtype=np.int32)
_XLOOP_NBITS = len(_XLOOP_BITS_NP)          # 63


def _pf2_triple(a):
    return pf2_add(pf2_add(a, a), a)


def _pf_dbl_step(Rp):
    Rx, Ry, Rz = Rp
    b2 = (_c("b2_0"), _c("b2_1"))
    s1 = pf2_mul_many(
        [(Ry, Ry), (Rz, Rz), (pf2_add(Ry, Rz), pf2_add(Ry, Rz)), (Rx, Rx), (Rx, Ry)])
    t0, t1, u, v, m = s1
    t2 = _pf2_triple(pf2_mul(t1, b2))
    t3 = _pf2_triple(t2)
    t4 = pf2_sub(pf2_sub(u, t1), t0)
    ell = (pf2_sub(t2, t0), _pf2_triple(v), pf2_neg(t4))
    half = _c("half")
    hs = pf_mul_many([(pf2_add(t0, t3)[0], half), (pf2_add(t0, t3)[1], half),
                      (pf2_sub(t0, t3)[0], half), (pf2_sub(t0, t3)[1], half)])
    hh = (hs[0], hs[1])
    g = (hs[2], hs[3])
    s3 = pf2_mul_many([(hh, hh), (t2, t2), (g, m), (t0, t4)])
    Ry2 = pf2_sub(s3[0], _pf2_triple(s3[1]))
    return (s3[2], Ry2, s3[3]), ell


def _pf_add_step(Rp, Q):
    Rx, Ry, Rz = Rp
    Qx, Qy = Q
    s1 = pf2_mul_many([(Qy, Rz), (Qx, Rz)])
    t0 = pf2_sub(Ry, s1[0])
    t1 = pf2_sub(Rx, s1[1])
    s2 = pf2_mul_many([(t0, Qx), (t1, Qy), (t1, t1), (t0, t0)])
    ell = (pf2_sub(s2[0], s2[1]), pf2_neg(t0), t1)
    t2 = s2[2]
    s3 = pf2_mul_many([(t2, t1), (t2, Rx), (s2[3], Rz)])
    t3, t4, t0sqRz = s3
    t5 = pf2_add(pf2_sub(t3, pf2_add(t4, t4)), t0sqRz)
    s4 = pf2_mul_many([(t1, t5), (pf2_sub(t4, t5), t0), (t3, Ry), (Rz, t3)])
    return (s4[0], pf2_sub(s4[1], s4[2]), s4[3]), ell


def _pf_apply_line(f, ell, px, py):
    o1 = pf2_mul_fp(ell[1], px)
    o4 = pf2_mul_fp(ell[2], py)
    z = pf2_zeros(px.shape[-1:])
    sp = ((ell[0], o1, z), (z, o4, z))
    return pf12_mul(f, sp)


def _miller_math(getbit, px, py, q2, nbits: int):
    shape = px.shape[-1:]
    f0 = pf12_ones(shape)
    R0 = (q2[0], q2[1], pf2_ones(shape))

    def step(i, carry):
        f, Rp = carry
        f = pf12_sqr(f)
        Rp, ell = _pf_dbl_step(Rp)
        f = _pf_apply_line(f, ell, px, py)

        def add_branch(args):
            fa, Ra = args
            Ra, ell_a = _pf_add_step(Ra, q2)
            return _pf_apply_line(fa, ell_a, px, py), Ra

        return _maybe_cond(getbit(i), add_branch, (f, Rp))

    f, _ = jax.lax.fori_loop(0, nbits, step, (f0, R0))
    return pf12_conj(f)


# The final exponentiation runs as a straight-line program over a few
# Fp12 registers, interpreted by ONE loop whose body holds a single
# squaring and a single multiplication site.  Unrolled (5 |x|-power
# loops + ~10 products, each its own copy of the Fp12 formulas) the
# kernel took 343 s to compile for v5e (AOT, PR 21); one body site per
# operation is what the compile time scales with.  Each step reads an
# (op, operand) code from SMEM; `lax.switch` runs only the taken branch.
_FE_SQR, _FE_MUL, _FE_SET, _FE_SAVE_G, _FE_SAVE_F, _FE_SAVE_E2 = range(6)
# operands: 0 G, 1 conj G, 2 frob1 G, 3 frob2 G, 4 F, 5 conj F, 6 inv,
# 7 conj A, 8 frob2 E2, 9 conj E2
_FE_NARG = 10


def _fe_program() -> np.ndarray:
    """The final exponentiation f^((p^12-1)/r) as (op << 4 | operand)
    codes — the same addition chain as ops/pairing.py:
      easy part   f <- conj(f)·f^-1, then f <- frob2(f)·f
      pow_x(g)    = conj(g^|x|)  (x < 0; cyclotomic inverse = conj)
      e1 = pow_x(f)·conj(f);  e1 = pow_x(e1)·conj(e1)
      e2 = pow_x(e1)·frob1(e1)
      e3 = pow_x(pow_x(e2))·frob2(e2)·conj(e2);  result e3·f^3."""
    code = []
    op = lambda o, a=0: code.append(o << 4 | a)  # noqa: E731
    op(_FE_SET, 5)
    op(_FE_MUL, 6)                      # conj(f) * f^-1
    op(_FE_SAVE_G)
    op(_FE_SET, 3)
    op(_FE_MUL, 0)                      # frob2(f) * f
    op(_FE_SAVE_F)
    op(_FE_SAVE_G)
    posts = [[5], [1], [2], [], [8, 9, 4, 4, 4]]
    for stage, post in enumerate(posts):
        op(_FE_SET, 0)                  # A = g
        for bit in _XLOOP_BITS_NP:      # A = g^|x|
            op(_FE_SQR)
            if bit:
                op(_FE_MUL, 0)
        op(_FE_SET, 7)                  # A = pow_x(g)
        for a in post:
            op(_FE_MUL, a)
        if stage == 2:
            op(_FE_SAVE_E2)             # E2 = e2 (stage 3's input)
        op(_FE_SAVE_G)
    return np.asarray(code, np.int32)


def _finalexp_math(getcode, f, ncode: int):
    inv = pf12_inv(f)
    operands = [
        lambda r: r[1], lambda r: pf12_conj(r[1]),
        lambda r: pf12_frobenius(r[1], 1), lambda r: pf12_frobenius(r[1], 2),
        lambda r: r[2], lambda r: pf12_conj(r[2]), lambda r: r[4],
        lambda r: pf12_conj(r[0]), lambda r: pf12_frobenius(r[3], 2),
        lambda r: pf12_conj(r[3])]
    assert len(operands) == _FE_NARG

    def step(i, regs):                  # regs = (A, G, F, E2, inv)
        code = getcode(i)
        x = jax.lax.switch(code & 15, operands, regs)
        a, g, f_, e2, iv = regs
        return jax.lax.switch(code >> 4, [
            lambda: (pf12_sqr(a), g, f_, e2, iv),
            lambda: (pf12_mul(a, x), g, f_, e2, iv),
            lambda: (x, g, f_, e2, iv),
            lambda: (a, a, f_, e2, iv),
            lambda: (a, g, a, e2, iv),
            lambda: (a, g, f_, a, iv)])

    # every register is written before it is read (the program opens
    # with a SET); seeding them from data keeps one lane layout in the
    # loop carry — Mosaic refuses a carry seeded from broadcast constants
    regs = jax.lax.fori_loop(0, ncode, step, (f, f, f, f, inv))
    return regs[0]


_FE_CODE_NP = _fe_program()


def _flat12(f):
    return [x for c6 in f for c2 in c6 for x in c2]


def _pack12(arrs):
    it = iter(arrs)
    fp2 = lambda: (next(it), next(it))
    fp6 = lambda: (fp2(), fp2(), fp2())
    return (fp6(), fp6())


# The fp12 final-exp body holds several live fp12 values; at 256 lanes its
# VMEM footprint exceeds the 16M scoped limit, so the pairing kernels run on
# 128-lane tiles (their batches are tiny anyway — 2 lanes in the RLC path).
PAIR_TILE = 128
_BUNDLE_SPEC3 = lambda: pl.BlockSpec((NCONST, NL, PAIR_TILE),
                                     lambda i, *_: (0, 0, 0))


@lru_cache(maxsize=None)
def _miller_call(btot: int):
    def kernel(bits_ref, consts_ref, *refs):
        with _kernel_consts(consts=consts_ref[:, :, 0:1]):
            ins, outs = refs[:6], refs[6:]
            px, py = ins[0][:], ins[1][:]
            q2 = ((ins[2][:], ins[3][:]), (ins[4][:], ins[5][:]))
            f = _miller_math(lambda i: bits_ref[i], px, py, q2, _XLOOP_NBITS)
            for o, v in zip(outs, _flat12(f)):
                o[:] = v

    spec = pl.BlockSpec((NL, PAIR_TILE), lambda i, b: (0, i))
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(btot // PAIR_TILE,),
        in_specs=[_BUNDLE_SPEC3()] + [spec] * 6,
        out_specs=[spec] * 12,
    )
    return pl.pallas_call(
        kernel, grid_spec=gs, name="miller_loop_kernel",
        out_shape=[jax.ShapeDtypeStruct((NL, btot), U32)] * 12)


@lru_cache(maxsize=None)
def _miller_direct():
    @jax.jit
    def run(bits, *arrs):
        px, py = arrs[0], arrs[1]
        q2 = ((arrs[2], arrs[3]), (arrs[4], arrs[5]))
        f = _miller_math(lambda i: bits[i], px, py, q2, _XLOOP_NBITS)
        return tuple(_flat12(f))

    return run


@lru_cache(maxsize=None)
def _finalexp_call(btot: int):
    def kernel(code_ref, invbits_ref, consts_ref, *refs):
        with _kernel_consts(consts=consts_ref[:, :, 0:1],
                            invbit=lambda i: invbits_ref[i]):
            ins, outs = refs[:12], refs[12:]
            f = _pack12([r[:] for r in ins])
            out = _finalexp_math(lambda i: code_ref[i], f, len(_FE_CODE_NP))
            for o, v in zip(outs, _flat12(out)):
                o[:] = v

    spec = pl.BlockSpec((NL, PAIR_TILE), lambda i, b1, b2: (0, i))
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(btot // PAIR_TILE,),
        in_specs=[pl.BlockSpec((NCONST, NL, PAIR_TILE),
                               lambda i, b1, b2: (0, 0, 0))]
        + [spec] * 12,
        out_specs=[spec] * 12,
    )
    return pl.pallas_call(
        kernel, grid_spec=gs, name="final_exp_kernel",
        out_shape=[jax.ShapeDtypeStruct((NL, btot), U32)] * 12)


@lru_cache(maxsize=None)
def _finalexp_direct():
    @jax.jit
    def run(code, invbits, *arrs):
        with _kernel_consts(invbit=lambda i: invbits[i]):
            f = _pack12(list(arrs))
            return tuple(_flat12(_finalexp_math(lambda i: code[i], f,
                                                len(_FE_CODE_NP))))

    return run


def miller_loop(px, py, q2):
    """Drop-in for pairing.miller_loop (XLA layout in/out)."""
    flat_in = [px, py, q2[0][0], q2[0][1], q2[1][0], q2[1][1]]
    shape = px.shape[:-1]
    b = int(np.prod(shape)) if shape else 1
    lanes = [_to_lanes(x, PAIR_TILE)[0] for x in flat_in]
    btot = lanes[0].shape[1]
    bits = jnp.asarray(_XLOOP_BITS_NP)
    if _use_kernels():
        out = _miller_call(btot)(bits, _const_bundle(PAIR_TILE), *lanes)
    else:
        out = _miller_direct()(bits, *lanes)
    leaves = [_from_lanes(x, shape, b) for x in out]
    return _pack12(leaves)


def final_exponentiation(f):
    """Drop-in for pairing.final_exponentiation (XLA layout in/out)."""
    flat_in = _flat12(f)
    shape = flat_in[0].shape[:-1]
    b = int(np.prod(shape)) if shape else 1
    lanes = [_to_lanes(x, PAIR_TILE)[0] for x in flat_in]
    btot = lanes[0].shape[1]
    code = jnp.asarray(_FE_CODE_NP)
    invbits = jnp.asarray(_inv_bits())
    if _use_kernels():
        out = _finalexp_call(btot)(code, invbits,
                                   _const_bundle(PAIR_TILE), *lanes)
    else:
        out = _finalexp_direct()(code, invbits, *lanes)
    leaves = [_from_lanes(x, shape, b) for x in out]
    return _pack12(leaves)


# ---------------------------------------------------------------------------
# Point-sum tree reduction: collapse a point batch across the lane axis
# inside one kernel (replaces DevCurve.sum_points' log2(n) XLA rounds, each
# a separate latency-bound dispatch).  Grid tiles reduce to one point per
# tile; the caller folds the (few) per-tile partials in XLA.
# ---------------------------------------------------------------------------


def _sum_tile_math(kind: str, pt, roll=None):
    """Reduce a (…, 24, W) point across lanes: log2(W) rotate-and-add levels
    at CONSTANT width (Mosaic rejects the narrowing layouts a halving tree
    produces below 128 lanes).  The levels are ONE loop body with a
    dynamic rotate (W/2, W/4, …, 1 lanes): an unrolled level chain
    compiled for minutes per kernel (v5e AOT: 81 s for G1, 324 s for G2
    at 8 levels).  Cyclic rotate-and-add leaves the total in every lane,
    whichever way the rotate turns; callers read lane 0.  `roll(t, sh)`
    is the lane rotate (pltpu.roll inside the kernel; jnp.roll in XLA).
    W must be a power of two."""
    curve = _curve_of(kind)
    w = _flat_point(pt)[0].shape[-1]
    assert w & (w - 1) == 0, "rotate-and-add reduction needs power-of-two width"
    if roll is None:
        roll = lambda t, sh: jnp.roll(t, sh, axis=-1)  # noqa: E731

    def level(i, acc):
        sh = jnp.right_shift(jnp.int32(w // 2), i)
        return curve.add(acc, jax.tree.map(lambda t: roll(t, sh), acc))

    return jax.lax.fori_loop(0, w.bit_length() - 1, level, pt)


@lru_cache(maxsize=None)
def _sum_call(kind: str, btot: int):
    nc = _ncoord(kind)

    def kernel(p_ref, one_ref, *refs):
        with _kernel_consts(p=p_ref[:, 0:1], one=one_ref[:, 0:1]):
            ins, outs = refs[:nc], refs[nc:]
            pt = _pack_point(kind, [r[:] for r in ins])
            acc = _sum_tile_math(
                kind, pt,
                roll=lambda t, sh: pltpu.roll(t, sh, axis=t.ndim - 1))
            # a (24, 1) output tile violates Mosaic's lane-tiling minimum —
            # broadcast lane 0 (the sum) across the tile; the caller reads
            # lane 0 of each tile (strided slice in XLA)
            for o, v in zip(outs, _flat_point(acc)):
                o[:] = jnp.broadcast_to(v[..., 0:1], (NL, TILE))

    spec = pl.BlockSpec((NL, TILE), lambda i: (0, i))
    gs = pl.GridSpec(
        grid=(btot // TILE,),
        in_specs=[pl.BlockSpec((NL, TILE), lambda i: (0, 0))] * 2
        + [spec] * nc,
        out_specs=[spec] * nc,
    )
    return pl.pallas_call(
        kernel, grid_spec=gs, name=f"{kind.lower()}_point_sum_kernel",
        out_shape=[jax.ShapeDtypeStruct((NL, btot), U32)] * nc)


def sum_points(kind: str, p):
    """Drop-in for DevCurve.sum_points (leading-axis point reduction).

    Recursive: each kernel call reduces every TILE-lane tile to one point;
    the per-tile partials feed the next call (zero-padded lanes read as
    infinity, inert) until one tile remains.  At 8192 lanes that is TWO
    kernel dispatches and zero XLA-level group adds — the old single-level
    version folded 31 partials per sum with ~30 sequential XLA complete
    adds, which dominated both the HLO graph (compile time) and the
    sums-stage wall time (PERF.md r3 stage table)."""
    from . import curve as DC
    xla_curve = DC.G1_DEV if kind == "G1" else DC.G2_DEV
    shape = _flat_point(p)[0].shape[:-1]
    if len(shape) != 1 or not _use_kernels():
        return None                                  # caller falls back to XLA
    arrs, _, b = _point_to_lanes(p)
    while True:
        btot = arrs[0].shape[1]
        out = _sum_call(kind, btot)(_P_FULL, _ONE_FULL, *arrs)
        ntiles = btot // TILE
        out = [x[:, ::TILE] for x in out]            # lane 0 of each tile
        if ntiles == 1:
            partials = _point_from_lanes(kind, out, (1,), 1)
            return jax.tree.map(lambda t: t[0], partials)
        if ntiles <= 4:
            partials = _point_from_lanes(kind, out, (ntiles,), ntiles)
            acc = jax.tree.map(lambda t: t[0], partials)
            for i in range(1, ntiles):
                acc = xla_curve.add(acc, jax.tree.map(lambda t: t[i], partials))
            return acc
        # next level: per-tile partials become the lanes of a smaller call
        arrs = [jnp.pad(x, ((0, 0), (0, TILE - ntiles % TILE)))
                if ntiles % TILE else x for x in out]


# ---------------------------------------------------------------------------
# GLV joint ladders for RLC coefficients.
#
# G1: k = k0 + lambda*k1 with uniform 64-bit halves (lambda = -x^2 mod r,
# the phi eigenvalue: ops/curve.py g1_in_subgroup identity).  64 double+add
# steps instead of 128 — the RLC randomizers are SAMPLED in split form, so
# no decomposition is needed and per-coefficient soundness stays 2^-128
# (the map (k0,k1) -> k0+lambda*k1 is injective on [0,2^64)^2).
#
# G2: the same joint-ladder machinery with the psi^2 endomorphism
# (eigenvalue x^2; psi^2 scales affine coords by Fp constants, so the
# affine-table construction carries over verbatim).  Callers split the
# 128-bit coefficient 4 ways across psi via lane duplication (curve.py
# g2_glv_msm_terms), so nbits = 32 here.
# ---------------------------------------------------------------------------


def _pack_affine(kind: str, arrs):
    if kind == "G1":
        return (arrs[0], arrs[1])
    return ((arrs[0], arrs[1]), (arrs[2], arrs[3]))


def _naff(kind: str) -> int:
    return 2 if kind == "G1" else 4


def _ladder_glv_mixed_math(kind, getrow0, getrow1, pt, phi, p3, nbits: int):
    """Joint ladder over precomputed AFFINE tables {P, endo(P), P+endo(P)}
    (built outside the kernel in XLA — the in-kernel endo multiply and
    table add crashed the Mosaic compiler).  Affine bases make every
    table add a mixed addition: 18 vs 23 staged products."""
    curve = _curve_of(kind)
    acc0 = curve.infinity((_flat_point(pt)[0].shape[-1],))

    def sel(cond, a, b):
        return jax.tree.map(lambda x, y: jnp.where(cond, x, y), a, b)

    def step(i, acc):
        acc = curve.double(acc)
        b0 = getrow0(i) == 1                        # (1, B)
        b1 = getrow1(i) == 1
        t = sel(b0, sel(b1, p3, pt), sel(b1, phi, pt))
        added = curve.add_mixed(acc, t)
        return sel(b0 | b1, added, acc)

    return jax.lax.fori_loop(0, nbits, step, acc0)


@lru_cache(maxsize=None)
def _ladder_glv_mixed_call(kind: str, nbits: int, btot: int):
    na = _naff(kind)
    nc = _ncoord(kind)

    def kernel(p_ref, one_ref, *refs):
        with _kernel_consts(p=p_ref[:, 0:1], one=one_ref[:, 0:1]):
            ins = refs[:3 * na]
            b0_ref, b1_ref = refs[3 * na], refs[3 * na + 1]
            outs = refs[3 * na + 2:]
            pt = _pack_affine(kind, [r[:] for r in ins[:na]])
            phi = _pack_affine(kind, [r[:] for r in ins[na:2 * na]])
            p3 = _pack_affine(kind, [r[:] for r in ins[2 * na:]])
            acc = _ladder_glv_mixed_math(kind,
                                         lambda i: b0_ref[pl.ds(i, 1), :],
                                         lambda i: b1_ref[pl.ds(i, 1), :],
                                         pt, phi, p3, nbits)
            for o, v in zip(outs, _flat_point(acc)):
                o[:] = v

    spec = pl.BlockSpec((NL, TILE), lambda i: (0, i))
    bspec = pl.BlockSpec((nbits, TILE), lambda i: (0, i))
    gs = pl.GridSpec(
        grid=(btot // TILE,),
        in_specs=[pl.BlockSpec((NL, TILE), lambda i: (0, 0))] * 2
        + [spec] * (3 * na) + [bspec, bspec],
        out_specs=[spec] * nc,
    )
    return pl.pallas_call(
        kernel, grid_spec=gs, name=f"{kind.lower()}_glv_ladder_kernel",
        out_shape=[jax.ShapeDtypeStruct((NL, btot), U32)] * nc)


@lru_cache(maxsize=None)
def _ladder_glv_mixed_direct(kind: str, nbits: int):
    na = _naff(kind)

    @jax.jit
    def run(b0, b1, *arrs):
        pt = _pack_affine(kind, arrs[:na])
        phi = _pack_affine(kind, arrs[na:2 * na])
        p3 = _pack_affine(kind, arrs[2 * na:])
        sl = lambda b: (lambda i: jax.lax.dynamic_slice_in_dim(b, i, 1, 0))
        return tuple(_flat_point(
            _ladder_glv_mixed_math(kind, sl(b0), sl(b1), pt, phi, p3, nbits)))

    return run


def scalar_mul_glv_g1(p, bits0, bits1):
    """(k0 + lambda*k1)-weighted points, bits MSB-first (nbits,) + batch.

    The {P, phi(P), P+phi(P)} tables are normalized to AFFINE in XLA (one
    shared-chain batch inversion for P and P+phi(P) together, curve.py
    to_affine_batch), so every ladder step uses the cheaper complete mixed
    addition (18 vs 23 staged products)."""
    from . import curve as DC
    import jax.numpy as jn
    phi_jac = DC.g1_phi(p)
    p3_jac = DC.G1_DEV.add(p, phi_jac)
    cat = lambda a, b: jn.concatenate([a, b], 0)
    ax, ay, _ = DC.G1_DEV.to_affine_batch(
        (cat(p[0], p3_jac[0]), cat(p[1], p3_jac[1]), cat(p[2], p3_jac[2])))
    n = p[0].shape[0]
    pt = (ax[:n], ay[:n])
    p3 = (ax[n:], ay[n:])
    phi = (jn.asarray(L.mont_mul(jn.broadcast_to(DC._BETA_DEV, pt[0].shape),
                                 pt[0])), pt[1])
    out = scalar_mul_glv_mixed("G1", pt, phi, p3, bits0, bits1)
    # totality: k·infinity = infinity (affine tables cannot express it, so
    # restore it after the ladder; production inputs are never infinity)
    inf_in = DC.G1_DEV.is_infinity(p)
    return DC.G1_DEV._select(
        inf_in, DC.G1_DEV.infinity(DC.G1_DEV.f.batch_shape(p[0])), out)


def scalar_mul_glv_g2(p, bits0, bits1):
    """(k0 + x^2*k1)-weighted G2 points via the psi^2 joint ladder.

    psi^2 acts on affine coords as (n_x·x, n_y·y) with n_x, n_y in Fp
    (curve.py _PSI2_NX/_PSI2_NY), so the affine tables {Q, psi^2(Q),
    Q+psi^2(Q)} are built exactly like the G1 phi tables."""
    from . import curve as DC
    import jax.numpy as jn
    psi2_jac = DC.g2_psi2(p)
    p3_jac = DC.G2_DEV.add(p, psi2_jac)
    cat3 = lambda a, b: jax.tree.map(
        lambda x, y: jn.concatenate([x, y], 0), a, b)
    ax, ay, _ = DC.G2_DEV.to_affine_batch(cat3(p, p3_jac))
    n = p[0][0].shape[0]
    half = lambda c, lo: jax.tree.map(
        lambda t: t[:n] if lo else t[n:], c)
    pt = (half(ax, True), half(ay, True))
    p3 = (half(ax, False), half(ay, False))
    mulc = lambda c, k: jn.asarray(
        L.mont_mul(jn.broadcast_to(k, c.shape), c))
    phi = ((mulc(pt[0][0], DC._PSI2_NX_DEV), mulc(pt[0][1], DC._PSI2_NX_DEV)),
           (mulc(pt[1][0], DC._PSI2_NY_DEV), mulc(pt[1][1], DC._PSI2_NY_DEV)))
    out = scalar_mul_glv_mixed("G2", pt, phi, p3, bits0, bits1)
    inf_in = DC.G2_DEV.is_infinity(p)
    return DC.G2_DEV._select(
        inf_in, DC.G2_DEV.infinity(DC.G2_DEV.f.batch_shape(p[0][0])), out)


def scalar_mul_glv_mixed(kind, pt, phi, p3, bits0, bits1):
    """Joint GLV ladder over affine tables {P, endo(P), P+endo(P)}."""
    flat = _flat_point(pt) + _flat_point(phi) + _flat_point(p3)
    arrs = []
    shape = b = None
    for x in flat:
        lx, shape, b = _to_lanes(x)
        arrs.append(lx)
    nbits = bits0.shape[0]
    btot = arrs[0].shape[1]

    def prep(bits):
        bt = bits.reshape(nbits, b).astype(U32)
        return jnp.pad(bt, ((0, 0), (0, btot - b))) if btot != b else bt

    b0, b1 = prep(bits0), prep(bits1)
    if _use_kernels():
        out = _ladder_glv_mixed_call(kind, nbits, btot)(_P_FULL, _ONE_FULL,
                                                        *arrs, b0, b1)
    else:
        out = _ladder_glv_mixed_direct(kind, nbits)(b0, b1, *arrs)
    return _point_from_lanes(kind, out, shape, b)
