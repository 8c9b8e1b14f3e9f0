// BLS12-381 host cryptography — the native layer of drand_tpu.
//
// Role: the CPU latency path (single sign/verify, DKG share math, partial
// signing) that the reference delegates to kilc/bls12-381's x86-64 assembly
// (SURVEY.md §2.9).  The TPU/XLA kernels handle batch throughput; this
// library handles microsecond-scale host calls, loaded from Python via
// ctypes (drand_tpu/crypto/host/native.py) with the pure-Python tower as
// fallback and golden reference.
//
// Field layout mirrors drand_tpu/crypto/host/field.py:
//   Fp   : 6x64-bit limbs, Montgomery form (R = 2^384)
//   Fp2  : c0 + c1 u,         u^2 = -1
//   Fp6  : a + b v + c v^2,   v^3 = xi = 1 + u
//   Fp12 : a + b w,           w^2 = v
//
// The pairing is the optimal ate loop over |x| with affine G2 steps in Fp2
// and the line embedded sparsely into Fp12 (untwist (x,y) -> (x/w^2, y/w^3)
// folded into coefficient placement; every line is pre-scaled by the Fp2
// element xi — subfield factors die in the final exponentiation).  The
// final exponentiation matches host/pairing.py:117-129.
//
// Build: make -C native   (g++ -O3 -shared; no external dependencies).

#include <stdint.h>
#include <string.h>

#include "constants_gen.h"

typedef unsigned __int128 u128;

// ---------------------------------------------------------------------------
// Fp
// ---------------------------------------------------------------------------

struct fp { uint64_t l[6]; };

static const fp FP_ZERO = {{0, 0, 0, 0, 0, 0}};

static inline void fp_copy(fp &o, const fp &a) { o = a; }

static inline int fp_is_zero(const fp &a) {
  uint64_t r = 0;
  for (int i = 0; i < 6; i++) r |= a.l[i];
  return r == 0;
}

static inline int fp_eq(const fp &a, const fp &b) {
  uint64_t r = 0;
  for (int i = 0; i < 6; i++) r |= a.l[i] ^ b.l[i];
  return r == 0;
}

// a += b with carry out
static inline uint64_t add6(uint64_t *o, const uint64_t *a, const uint64_t *b) {
  u128 c = 0;
  for (int i = 0; i < 6; i++) {
    c += (u128)a[i] + b[i];
    o[i] = (uint64_t)c;
    c >>= 64;
  }
  return (uint64_t)c;
}

// o = a - b, returns borrow
static inline uint64_t sub6(uint64_t *o, const uint64_t *a, const uint64_t *b) {
  u128 br = 0;
  for (int i = 0; i < 6; i++) {
    u128 d = (u128)a[i] - b[i] - br;
    o[i] = (uint64_t)d;
    br = (d >> 64) & 1;
  }
  return (uint64_t)br;
}

static inline int geq6(const uint64_t *a, const uint64_t *b) {
  for (int i = 5; i >= 0; i--) {
    if (a[i] > b[i]) return 1;
    if (a[i] < b[i]) return 0;
  }
  return 1;
}

static inline void fp_add(fp &o, const fp &a, const fp &b) {
  uint64_t t[6];
  uint64_t carry = add6(t, a.l, b.l);
  uint64_t t2[6];
  uint64_t borrow = sub6(t2, t, BLS_P);
  // select t2 if no borrow (t >= p) or carry out happened
  uint64_t use_sub = carry | (borrow ^ 1);
  for (int i = 0; i < 6; i++) o.l[i] = use_sub ? t2[i] : t[i];
}

static inline void fp_sub(fp &o, const fp &a, const fp &b) {
  uint64_t t[6];
  uint64_t borrow = sub6(t, a.l, b.l);
  if (borrow) add6(t, t, BLS_P);
  memcpy(o.l, t, sizeof t);
}

static inline void fp_neg(fp &o, const fp &a) {
  if (fp_is_zero(a)) { o = FP_ZERO; return; }
  sub6(o.l, BLS_P, a.l);
}

// Montgomery multiplication (CIOS)
static void fp_mul(fp &out, const fp &x, const fp &y) {
  uint64_t t[8] = {0};
  for (int i = 0; i < 6; i++) {
    // t += x[i] * y
    u128 c = 0;
    for (int j = 0; j < 6; j++) {
      c += (u128)t[j] + (u128)x.l[i] * y.l[j];
      t[j] = (uint64_t)c;
      c >>= 64;
    }
    c += t[6];
    t[6] = (uint64_t)c;
    t[7] = (uint64_t)(c >> 64);
    // m = t[0] * n0inv mod 2^64 ; t += m*p ; t >>= 64
    uint64_t m = t[0] * BLS_N0INV;
    c = (u128)t[0] + (u128)m * BLS_P[0];
    c >>= 64;
    for (int j = 1; j < 6; j++) {
      c += (u128)t[j] + (u128)m * BLS_P[j];
      t[j - 1] = (uint64_t)c;
      c >>= 64;
    }
    c += t[6];
    t[5] = (uint64_t)c;
    t[6] = t[7] + (uint64_t)(c >> 64);
    t[7] = 0;
  }
  // final reduce
  if (t[6] || geq6(t, BLS_P)) sub6(t, t, BLS_P);
  memcpy(out.l, t, 6 * sizeof(uint64_t));
}

static inline void fp_sqr(fp &o, const fp &a) { fp_mul(o, a, a); }

static const fp FP_ONE = {{FP_ONE_MONT[0], FP_ONE_MONT[1], FP_ONE_MONT[2],
                           FP_ONE_MONT[3], FP_ONE_MONT[4], FP_ONE_MONT[5]}};

static void fp_to_mont(fp &o, const fp &raw) {
  fp r2;
  memcpy(r2.l, BLS_R2, sizeof r2.l);
  fp_mul(o, raw, r2);
}

static void fp_from_mont(fp &o, const fp &m) {
  fp one = {{1, 0, 0, 0, 0, 0}};
  fp_mul(o, m, one);
}

// o = a^e where e is `n` little-endian limbs (a in Montgomery form)
static void fp_pow(fp &o, const fp &a, const uint64_t *e, int n) {
  fp acc = FP_ONE, base = a;
  for (int i = 0; i < n; i++) {
    uint64_t w = e[i];
    for (int b = 0; b < 64; b++) {
      if (w & 1) { fp t; fp_mul(t, acc, base); acc = t; }
      fp t2; fp_sqr(t2, base); base = t2;
      w >>= 1;
    }
  }
  o = acc;
}

static void fp_inv(fp &o, const fp &a) { fp_pow(o, a, P_MINUS2, 6); }

static int fp_is_square(const fp &a) {
  if (fp_is_zero(a)) return 1;
  fp t;
  fp_pow(t, a, P_MINUS1_DIV2, 6);
  return fp_eq(t, FP_ONE);
}

// returns 0 and leaves o untouched when a is not a QR
static int fp_sqrt(fp &o, const fp &a) {
  fp s, s2;
  fp_pow(s, a, P_PLUS1_DIV4, 6);
  fp_sqr(s2, s);
  if (!fp_eq(s2, a)) return 0;
  o = s;
  return 1;
}

static int fp_sgn0(const fp &a) {
  fp raw;
  fp_from_mont(raw, a);
  return raw.l[0] & 1;
}

// raw (non-Montgomery) comparison helper: a > (p-1)/2 ?
static int fp_is_larger_half(const fp &mont_a) {
  fp raw;
  fp_from_mont(raw, mont_a);
  // compare raw > (p-1)/2  <=>  raw >= (p-1)/2 + 1 = (p+1)/2
  uint64_t half_plus[6];
  uint64_t one[6] = {1, 0, 0, 0, 0, 0};
  add6(half_plus, P_MINUS1_DIV2, one);
  return geq6(raw.l, half_plus);
}

// -- byte IO (big-endian 48) -------------------------------------------------

static int fp_from_bytes(fp &o, const uint8_t *b) {
  fp raw;
  for (int i = 0; i < 6; i++) {
    uint64_t w = 0;
    for (int j = 0; j < 8; j++) w = (w << 8) | b[(5 - i) * 8 + j];
    raw.l[i] = w;
  }
  if (geq6(raw.l, BLS_P) && !fp_is_zero(raw)) {
    // values must be < p
    if (geq6(raw.l, BLS_P)) return 0;
  }
  fp_to_mont(o, raw);
  return 1;
}

static void fp_to_bytes(uint8_t *b, const fp &m) {
  fp raw;
  fp_from_mont(raw, m);
  for (int i = 0; i < 6; i++) {
    uint64_t w = raw.l[5 - i];
    for (int j = 0; j < 8; j++) b[i * 8 + j] = (uint8_t)(w >> (8 * (7 - j)));
  }
}

// ---------------------------------------------------------------------------
// Fp2
// ---------------------------------------------------------------------------

struct fp2 { fp c0, c1; };

static const fp2 FP2_ZERO_ = {FP_ZERO, FP_ZERO};
static const fp2 FP2_ONE_ = {FP_ONE, FP_ZERO};

static inline int fp2_is_zero(const fp2 &a) {
  return fp_is_zero(a.c0) && fp_is_zero(a.c1);
}
static inline int fp2_eq(const fp2 &a, const fp2 &b) {
  return fp_eq(a.c0, b.c0) && fp_eq(a.c1, b.c1);
}
static inline void fp2_add(fp2 &o, const fp2 &a, const fp2 &b) {
  fp_add(o.c0, a.c0, b.c0);
  fp_add(o.c1, a.c1, b.c1);
}
static inline void fp2_sub(fp2 &o, const fp2 &a, const fp2 &b) {
  fp_sub(o.c0, a.c0, b.c0);
  fp_sub(o.c1, a.c1, b.c1);
}
static inline void fp2_neg(fp2 &o, const fp2 &a) {
  fp_neg(o.c0, a.c0);
  fp_neg(o.c1, a.c1);
}
static void fp2_mul(fp2 &o, const fp2 &a, const fp2 &b) {
  fp t0, t1, s0, s1, t2;
  fp_mul(t0, a.c0, b.c0);
  fp_mul(t1, a.c1, b.c1);
  fp_add(s0, a.c0, a.c1);
  fp_add(s1, b.c0, b.c1);
  fp_mul(t2, s0, s1);           // (a0+a1)(b0+b1)
  fp_sub(t2, t2, t0);
  fp_sub(t2, t2, t1);           // a0b1 + a1b0
  fp_sub(o.c0, t0, t1);
  o.c1 = t2;
}
static void fp2_sqr(fp2 &o, const fp2 &a) {
  fp s, d, m;
  fp_add(s, a.c0, a.c1);
  fp_sub(d, a.c0, a.c1);
  fp_mul(m, a.c0, a.c1);
  fp_mul(o.c0, s, d);
  fp_add(o.c1, m, m);
}
static inline void fp2_conj(fp2 &o, const fp2 &a) {
  o.c0 = a.c0;
  fp_neg(o.c1, a.c1);
}
static void fp2_inv(fp2 &o, const fp2 &a) {
  fp n, t, ni;
  fp_sqr(n, a.c0);
  fp_sqr(t, a.c1);
  fp_add(n, n, t);              // norm
  fp_inv(ni, n);
  fp_mul(o.c0, a.c0, ni);
  fp neg1;
  fp_neg(neg1, a.c1);
  fp_mul(o.c1, neg1, ni);
}
static inline void fp2_mul_fp(fp2 &o, const fp2 &a, const fp &k) {
  fp_mul(o.c0, a.c0, k);
  fp_mul(o.c1, a.c1, k);
}
// a * xi, xi = 1 + u:  (c0 - c1) + (c0 + c1) u
static inline void fp2_mul_xi(fp2 &o, const fp2 &a) {
  fp t0, t1;
  fp_sub(t0, a.c0, a.c1);
  fp_add(t1, a.c0, a.c1);
  o.c0 = t0;
  o.c1 = t1;
}
static void fp2_scalar_small(fp2 &o, const fp2 &a, int k) {
  // multiply by a small non-negative integer via repeated additions
  fp2 acc = FP2_ZERO_;
  for (int i = 0; i < k; i++) fp2_add(acc, acc, a);
  o = acc;
}

static int fp2_is_square(const fp2 &a) {
  fp n, t;
  fp_sqr(n, a.c0);
  fp_sqr(t, a.c1);
  fp_add(n, n, t);
  return fp_is_square(n);
}

static int fp2_sqrt(fp2 &o, const fp2 &a) {
  // mirrors host/field.py:139-166 (p = 3 mod 4, norm trick)
  if (fp_is_zero(a.c1)) {
    fp s;
    if (fp_sqrt(s, a.c0)) { o.c0 = s; o.c1 = FP_ZERO; return 1; }
    fp na;
    fp_neg(na, a.c0);
    if (fp_sqrt(s, na)) { o.c0 = FP_ZERO; o.c1 = s; return 1; }
    return 0;
  }
  fp n, t, d;
  fp_sqr(n, a.c0);
  fp_sqr(t, a.c1);
  fp_add(n, n, t);
  if (!fp_sqrt(d, n)) return 0;
  // x^2 = (a0 + d)/2 ; inv2 = (p+1)/2 as Montgomery constant
  fp inv2, two;
  fp_add(two, FP_ONE, FP_ONE);
  fp_inv(inv2, two);
  fp x2, x;
  fp_add(x2, a.c0, d);
  fp_mul(x2, x2, inv2);
  if (!fp_sqrt(x, x2)) {
    fp_sub(x2, a.c0, d);
    fp_mul(x2, x2, inv2);
    if (!fp_sqrt(x, x2)) return 0;
  }
  fp twox, tinv;
  fp_add(twox, x, x);
  fp_inv(tinv, twox);
  o.c0 = x;
  fp_mul(o.c1, a.c1, tinv);
  return 1;
}

static int fp2_sgn0(const fp2 &a) {
  // RFC 9380 sgn0 m=2 (host/field.py:169-174)
  int sign_0 = fp_sgn0(a.c0);
  int zero_0 = fp_is_zero(a.c0);
  int sign_1 = fp_sgn0(a.c1);
  return sign_0 | (zero_0 & sign_1);
}

static int fp2_is_larger_half(const fp2 &y) {
  if (!fp_is_zero(y.c1)) return fp_is_larger_half(y.c1);
  return fp_is_larger_half(y.c0);
}

// ---------------------------------------------------------------------------
// Fp6 / Fp12
// ---------------------------------------------------------------------------

struct fp6 { fp2 a, b, c; };
struct fp12 { fp6 a, b; };

static const fp6 FP6_ZERO_ = {FP2_ZERO_, FP2_ZERO_, FP2_ZERO_};
static const fp6 FP6_ONE_ = {FP2_ONE_, FP2_ZERO_, FP2_ZERO_};
static const fp12 FP12_ONE_ = {FP6_ONE_, FP6_ZERO_};

static inline void fp6_add(fp6 &o, const fp6 &x, const fp6 &y) {
  fp2_add(o.a, x.a, y.a);
  fp2_add(o.b, x.b, y.b);
  fp2_add(o.c, x.c, y.c);
}
static inline void fp6_sub(fp6 &o, const fp6 &x, const fp6 &y) {
  fp2_sub(o.a, x.a, y.a);
  fp2_sub(o.b, x.b, y.b);
  fp2_sub(o.c, x.c, y.c);
}
static inline void fp6_neg(fp6 &o, const fp6 &x) {
  fp2_neg(o.a, x.a);
  fp2_neg(o.b, x.b);
  fp2_neg(o.c, x.c);
}
static void fp6_mul(fp6 &o, const fp6 &x, const fp6 &y) {
  // host/field.py:203-215
  fp2 t0, t1, t2, s, u, c0, c1, c2;
  fp2_mul(t0, x.a, y.a);
  fp2_mul(t1, x.b, y.b);
  fp2_mul(t2, x.c, y.c);
  // c0 = t0 + xi*((a1+a2)(b1+b2) - t1 - t2)
  fp2_add(s, x.b, x.c);
  fp2_add(u, y.b, y.c);
  fp2_mul(c0, s, u);
  fp2_sub(c0, c0, t1);
  fp2_sub(c0, c0, t2);
  fp2_mul_xi(c0, c0);
  fp2_add(c0, c0, t0);
  // c1 = (a0+a1)(b0+b1) - t0 - t1 + xi*t2
  fp2_add(s, x.a, x.b);
  fp2_add(u, y.a, y.b);
  fp2_mul(c1, s, u);
  fp2_sub(c1, c1, t0);
  fp2_sub(c1, c1, t1);
  fp2 xt2;
  fp2_mul_xi(xt2, t2);
  fp2_add(c1, c1, xt2);
  // c2 = (a0+a2)(b0+b2) - t0 - t2 + t1
  fp2_add(s, x.a, x.c);
  fp2_add(u, y.a, y.c);
  fp2_mul(c2, s, u);
  fp2_sub(c2, c2, t0);
  fp2_sub(c2, c2, t2);
  fp2_add(c2, c2, t1);
  o.a = c0;
  o.b = c1;
  o.c = c2;
}
static inline void fp6_sqr(fp6 &o, const fp6 &x) { fp6_mul(o, x, x); }
// x * v: (a, b, c) -> (xi*c, a, b)
static inline void fp6_mul_by_v(fp6 &o, const fp6 &x) {
  fp2 t;
  fp2_mul_xi(t, x.c);
  fp2 a = x.a, b = x.b;
  o.a = t;
  o.b = a;
  o.c = b;
}
static void fp6_inv(fp6 &o, const fp6 &x) {
  // host/field.py:227-234
  fp2 c0, c1, c2, t, tmp, ti;
  fp2_sqr(c0, x.a);
  fp2_mul(tmp, x.b, x.c);
  fp2_mul_xi(tmp, tmp);
  fp2_sub(c0, c0, tmp);
  fp2_sqr(c1, x.c);
  fp2_mul_xi(c1, c1);
  fp2_mul(tmp, x.a, x.b);
  fp2_sub(c1, c1, tmp);
  fp2_sqr(c2, x.b);
  fp2_mul(tmp, x.a, x.c);
  fp2_sub(c2, c2, tmp);
  fp2 u;
  fp2_mul(t, x.b, c2);
  fp2_mul(tmp, x.c, c1);
  fp2_add(t, t, tmp);
  fp2_mul_xi(t, t);
  fp2_mul(u, x.a, c0);
  fp2_add(t, t, u);
  fp2_inv(ti, t);
  fp2_mul(o.a, c0, ti);
  fp2_mul(o.b, c1, ti);
  fp2_mul(o.c, c2, ti);
}

static inline void fp12_mul(fp12 &o, const fp12 &x, const fp12 &y) {
  fp6 t0, t1, s, u, c0, c1;
  fp6_mul(t0, x.a, y.a);
  fp6_mul(t1, x.b, y.b);
  fp6_mul_by_v(c0, t1);
  fp6_add(c0, c0, t0);
  fp6_add(s, x.a, x.b);
  fp6_add(u, y.a, y.b);
  fp6_mul(c1, s, u);
  fp6_sub(c1, c1, t0);
  fp6_sub(c1, c1, t1);
  o.a = c0;
  o.b = c1;
}
static void fp12_sqr(fp12 &o, const fp12 &x) {
  // host/field.py:262-267
  fp6 t, c0, s, u;
  fp6_mul(t, x.a, x.b);
  fp6_add(s, x.a, x.b);
  fp6_mul_by_v(u, x.b);
  fp6_add(u, u, x.a);
  fp6_mul(c0, s, u);
  fp6_sub(c0, c0, t);
  fp6 vt;
  fp6_mul_by_v(vt, t);
  fp6_sub(c0, c0, vt);
  o.a = c0;
  fp6_add(o.b, t, t);
}
static inline void fp12_conj(fp12 &o, const fp12 &x) {
  o.a = x.a;
  fp6_neg(o.b, x.b);
}
static void fp12_inv(fp12 &o, const fp12 &x) {
  fp6 t, u, ti;
  fp6_sqr(t, x.a);
  fp6_sqr(u, x.b);
  fp6_mul_by_v(u, u);
  fp6_sub(t, t, u);
  fp6_inv(ti, t);
  fp6_mul(o.a, x.a, ti);
  fp6 nb;
  fp6_mul(nb, x.b, ti);
  fp6_neg(o.b, nb);
}
static int fp12_is_one(const fp12 &x) {
  return fp2_eq(x.a.a, FP2_ONE_) && fp2_is_zero(x.a.b) &&
         fp2_is_zero(x.a.c) && fp2_is_zero(x.b.a) && fp2_is_zero(x.b.b) &&
         fp2_is_zero(x.b.c);
}

// Frobenius: a^(p^j), j in {1,2,3}, gammas from constants_gen.h
static void load_fp2(fp2 &o, const uint64_t *src) {
  memcpy(o.c0.l, src, 6 * sizeof(uint64_t));
  memcpy(o.c1.l, src + 6, 6 * sizeof(uint64_t));
}

static void fp12_frobenius(fp12 &o, const fp12 &x, int j) {
  const uint64_t *g = (j == 1) ? FROB_GAMMA1 : (j == 2) ? FROB_GAMMA2
                                                        : FROB_GAMMA3;
  // coefficient order over Fp2: a = c0 + c2 v + c4 v^2 ; b = c1 + c3 v + c5 v^2
  const fp2 *cs[6] = {&x.a.a, &x.b.a, &x.a.b, &x.b.b, &x.a.c, &x.b.c};
  fp2 *os[6] = {&o.a.a, &o.b.a, &o.a.b, &o.b.b, &o.a.c, &o.b.c};
  for (int i = 0; i < 6; i++) {
    fp2 t = *cs[i];
    if (j & 1) fp2_conj(t, t);
    fp2 gamma;
    load_fp2(gamma, g + 12 * i);
    fp2_mul(*os[i], t, gamma);
  }
}

// ---------------------------------------------------------------------------
// Curves: G1 (Jacobian over Fp), G2 (Jacobian over Fp2)
// ---------------------------------------------------------------------------

// generic jacobian point arithmetic via macro-free duplication (G1 then G2)

struct g1p { fp x, y, z; };   // z == 0 -> infinity
struct g2p { fp2 x, y, z; };

static inline int g1_is_inf(const g1p &p) { return fp_is_zero(p.z); }
static inline int g2_is_inf(const g2p &p) { return fp2_is_zero(p.z); }

static const g1p G1_INF = {FP_ZERO, FP_ZERO, FP_ZERO};
static const g2p G2_INF = {FP2_ZERO_, FP2_ZERO_, FP2_ZERO_};

static void g1_double(g1p &o, const g1p &in) {
  if (g1_is_inf(in) || fp_is_zero(in.y)) { o = G1_INF; return; }
  const g1p p = in;   // o may alias in
  fp A, B, C, D, E, F_, t;
  fp_sqr(A, p.x);
  fp_sqr(B, p.y);
  fp_sqr(C, B);
  fp_add(t, p.x, B);
  fp_sqr(D, t);
  fp_sub(D, D, A);
  fp_sub(D, D, C);
  fp_add(D, D, D);
  fp_add(E, A, A);
  fp_add(E, E, A);
  fp_sqr(F_, E);
  fp twoD;
  fp_add(twoD, D, D);
  fp_sub(o.x, F_, twoD);
  fp c8;
  fp_add(c8, C, C);
  fp_add(c8, c8, c8);
  fp_add(c8, c8, c8);
  fp dm;
  fp_sub(dm, D, o.x);
  fp_mul(o.y, E, dm);
  fp_sub(o.y, o.y, c8);
  fp yz;
  fp_add(yz, p.y, p.y);
  fp_mul(o.z, yz, p.z);
}

static void g1_add(g1p &o, const g1p &pin, const g1p &qin) {
  if (g1_is_inf(pin)) { o = qin; return; }
  if (g1_is_inf(qin)) { o = pin; return; }
  const g1p p = pin, q = qin;   // o may alias either input
  fp z1z1, z2z2, u1, u2, s1, s2, t;
  fp_sqr(z1z1, p.z);
  fp_sqr(z2z2, q.z);
  fp_mul(u1, p.x, z2z2);
  fp_mul(u2, q.x, z1z1);
  fp_mul(t, q.z, z2z2);
  fp_mul(s1, p.y, t);
  fp_mul(t, p.z, z1z1);
  fp_mul(s2, q.y, t);
  if (fp_eq(u1, u2)) {
    if (fp_eq(s1, s2)) { g1_double(o, p); return; }
    o = G1_INF;
    return;
  }
  fp h, i, j, r, v;
  fp_sub(h, u2, u1);
  fp_add(t, h, h);
  fp_sqr(i, t);
  fp_mul(j, h, i);
  fp_sub(r, s2, s1);
  fp_add(r, r, r);
  fp_mul(v, u1, i);
  fp_sqr(o.x, r);
  fp_sub(o.x, o.x, j);
  fp twoV;
  fp_add(twoV, v, v);
  fp_sub(o.x, o.x, twoV);
  fp_sub(t, v, o.x);
  fp_mul(o.y, r, t);
  fp s1j;
  fp_mul(s1j, s1, j);
  fp_add(s1j, s1j, s1j);
  fp_sub(o.y, o.y, s1j);
  fp zz;
  fp_add(zz, p.z, q.z);
  fp_sqr(zz, zz);
  fp_sub(zz, zz, z1z1);
  fp_sub(zz, zz, z2z2);
  fp_mul(o.z, zz, h);
}

static void g2_double(g2p &o, const g2p &in) {
  if (g2_is_inf(in) || fp2_is_zero(in.y)) { o = G2_INF; return; }
  const g2p p = in;   // o may alias in
  fp2 A, B, C, D, E, F_, t;
  fp2_sqr(A, p.x);
  fp2_sqr(B, p.y);
  fp2_sqr(C, B);
  fp2_add(t, p.x, B);
  fp2_sqr(D, t);
  fp2_sub(D, D, A);
  fp2_sub(D, D, C);
  fp2_add(D, D, D);
  fp2_add(E, A, A);
  fp2_add(E, E, A);
  fp2_sqr(F_, E);
  fp2 twoD;
  fp2_add(twoD, D, D);
  fp2_sub(o.x, F_, twoD);
  fp2 c8;
  fp2_add(c8, C, C);
  fp2_add(c8, c8, c8);
  fp2_add(c8, c8, c8);
  fp2 dm;
  fp2_sub(dm, D, o.x);
  fp2_mul(o.y, E, dm);
  fp2_sub(o.y, o.y, c8);
  fp2 yz;
  fp2_add(yz, p.y, p.y);
  fp2_mul(o.z, yz, p.z);
}

static void g2_add(g2p &o, const g2p &pin, const g2p &qin) {
  if (g2_is_inf(pin)) { o = qin; return; }
  if (g2_is_inf(qin)) { o = pin; return; }
  const g2p p = pin, q = qin;   // o may alias either input
  fp2 z1z1, z2z2, u1, u2, s1, s2, t;
  fp2_sqr(z1z1, p.z);
  fp2_sqr(z2z2, q.z);
  fp2_mul(u1, p.x, z2z2);
  fp2_mul(u2, q.x, z1z1);
  fp2_mul(t, q.z, z2z2);
  fp2_mul(s1, p.y, t);
  fp2_mul(t, p.z, z1z1);
  fp2_mul(s2, q.y, t);
  if (fp2_eq(u1, u2)) {
    if (fp2_eq(s1, s2)) { g2_double(o, p); return; }
    o = G2_INF;
    return;
  }
  fp2 h, i, j, r, v;
  fp2_sub(h, u2, u1);
  fp2_add(t, h, h);
  fp2_sqr(i, t);
  fp2_mul(j, h, i);
  fp2_sub(r, s2, s1);
  fp2_add(r, r, r);
  fp2_mul(v, u1, i);
  fp2_sqr(o.x, r);
  fp2_sub(o.x, o.x, j);
  fp2 twoV;
  fp2_add(twoV, v, v);
  fp2_sub(o.x, o.x, twoV);
  fp2_sub(t, v, o.x);
  fp2_mul(o.y, r, t);
  fp2 s1j;
  fp2_mul(s1j, s1, j);
  fp2_add(s1j, s1j, s1j);
  fp2_sub(o.y, o.y, s1j);
  fp2 zz;
  fp2_add(zz, p.z, q.z);
  fp2_sqr(zz, zz);
  fp2_sub(zz, zz, z1z1);
  fp2_sub(zz, zz, z2z2);
  fp2_mul(o.z, zz, h);
}

static void g1_neg(g1p &o, const g1p &p) {
  o = p;
  fp_neg(o.y, p.y);
}
static void g2_neg(g2p &o, const g2p &p) {
  o = p;
  fp2_neg(o.y, p.y);
}

// scalar mul, scalar = n little-endian 64-bit limbs, MSB-first double&add
static void g1_mul(g1p &o, const g1p &p, const uint64_t *k, int n) {
  g1p acc = G1_INF;
  int started = 0;
  for (int i = n - 1; i >= 0; i--) {
    for (int b = 63; b >= 0; b--) {
      if (started) g1_double(acc, acc);
      if ((k[i] >> b) & 1) {
        if (started) g1_add(acc, acc, p);
        else { acc = p; started = 1; }
      }
    }
  }
  o = started ? acc : G1_INF;
}

static void g2_mul(g2p &o, const g2p &p, const uint64_t *k, int n) {
  g2p acc = G2_INF;
  int started = 0;
  for (int i = n - 1; i >= 0; i--) {
    for (int b = 63; b >= 0; b--) {
      if (started) g2_double(acc, acc);
      if ((k[i] >> b) & 1) {
        if (started) g2_add(acc, acc, p);
        else { acc = p; started = 1; }
      }
    }
  }
  o = started ? acc : G2_INF;
}

// to affine
static void g1_affine(fp &x, fp &y, int &inf, const g1p &p) {
  if (g1_is_inf(p)) { inf = 1; return; }
  inf = 0;
  fp zi, zi2, zi3;
  fp_inv(zi, p.z);
  fp_sqr(zi2, zi);
  fp_mul(zi3, zi2, zi);
  fp_mul(x, p.x, zi2);
  fp_mul(y, p.y, zi3);
}
static void g2_affine(fp2 &x, fp2 &y, int &inf, const g2p &p) {
  if (g2_is_inf(p)) { inf = 1; return; }
  inf = 0;
  fp2 zi, zi2, zi3;
  fp2_inv(zi, p.z);
  fp2_sqr(zi2, zi);
  fp2_mul(zi3, zi2, zi);
  fp2_mul(x, p.x, zi2);
  fp2_mul(y, p.y, zi3);
}

static void g1_from_affine(g1p &o, const fp &x, const fp &y) {
  o.x = x;
  o.y = y;
  o.z = FP_ONE;
}
static void g2_from_affine(g2p &o, const fp2 &x, const fp2 &y) {
  o.x = x;
  o.y = y;
  o.z = FP2_ONE_;
}

static int g1_on_curve(const fp &x, const fp &y) {
  fp y2, x3, four;
  fp_sqr(y2, y);
  fp_sqr(x3, x);
  fp_mul(x3, x3, x);
  fp_add(four, FP_ONE, FP_ONE);
  fp_add(four, four, four);
  fp_add(x3, x3, four);
  return fp_eq(y2, x3);
}
static int g2_on_curve(const fp2 &x, const fp2 &y) {
  fp2 y2, x3, b;
  fp2_sqr(y2, y);
  fp2_sqr(x3, x);
  fp2_mul(x3, x3, x);
  load_fp2(b, FP2_B2);
  fp2_add(x3, x3, b);
  return fp2_eq(y2, x3);
}

static int g1_in_subgroup(const g1p &p) {
  g1p t;
  g1_mul(t, p, BLS_ORDER, 4);
  return g1_is_inf(t);
}
static int g2_in_subgroup(const g2p &p) {
  g2p t;
  g2_mul(t, p, BLS_ORDER, 4);
  return g2_is_inf(t);
}

// ---------------------------------------------------------------------------
// Serialization (ZCash compressed; host/serialize.py)
// ---------------------------------------------------------------------------

static int g1_decompress(g1p &o, const uint8_t *b, int check_subgroup) {
  uint8_t flags = b[0];
  if (!(flags & 0x80)) return 0;
  if (flags & 0x40) { o = G1_INF; return 1; }
  uint8_t xb[48];
  memcpy(xb, b, 48);
  xb[0] &= 0x1F;
  fp x;
  if (!fp_from_bytes(x, xb)) return 0;
  fp y2, x3, four, y;
  fp_sqr(x3, x);
  fp_mul(x3, x3, x);
  fp_add(four, FP_ONE, FP_ONE);
  fp_add(four, four, four);
  fp_add(y2, x3, four);
  if (!fp_sqrt(y, y2)) return 0;
  int larger = fp_is_larger_half(y);
  if (((flags & 0x20) != 0) != (larger != 0)) fp_neg(y, y);
  g1_from_affine(o, x, y);
  if (check_subgroup && !g1_in_subgroup(o)) return 0;
  return 1;
}

static void g1_compress(uint8_t *b, const g1p &p) {
  if (g1_is_inf(p)) {
    memset(b, 0, 48);
    b[0] = 0xC0;
    return;
  }
  fp x, y;
  int inf;
  g1_affine(x, y, inf, p);
  fp_to_bytes(b, x);
  b[0] |= 0x80;
  if (fp_is_larger_half(y)) b[0] |= 0x20;
}

static int g2_decompress(g2p &o, const uint8_t *b, int check_subgroup) {
  uint8_t flags = b[0];
  if (!(flags & 0x80)) return 0;
  if (flags & 0x40) { o = G2_INF; return 1; }
  uint8_t x1b[48];
  memcpy(x1b, b, 48);
  x1b[0] &= 0x1F;
  fp2 x;
  if (!fp_from_bytes(x.c1, x1b)) return 0;       // wire: x.c1 || x.c0
  if (!fp_from_bytes(x.c0, b + 48)) return 0;
  fp2 y2, x3, bb, y;
  fp2_sqr(x3, x);
  fp2_mul(x3, x3, x);
  load_fp2(bb, FP2_B2);
  fp2_add(y2, x3, bb);
  if (!fp2_sqrt(y, y2)) return 0;
  int larger = fp2_is_larger_half(y);
  if (((flags & 0x20) != 0) != (larger != 0)) fp2_neg(y, y);
  g2_from_affine(o, x, y);
  if (check_subgroup && !g2_in_subgroup(o)) return 0;
  return 1;
}

static void g2_compress(uint8_t *b, const g2p &p) {
  if (g2_is_inf(p)) {
    memset(b, 0, 96);
    b[0] = 0xC0;
    return;
  }
  fp2 x, y;
  int inf;
  g2_affine(x, y, inf, p);
  fp_to_bytes(b, x.c1);
  fp_to_bytes(b + 48, x.c0);
  b[0] |= 0x80;
  if (fp2_is_larger_half(y)) b[0] |= 0x20;
}

// ---------------------------------------------------------------------------
// Pairing (optimal ate; mirrors host/pairing.py with Fp2 affine steps)
// ---------------------------------------------------------------------------

// Line through T,T (doubling) or T,Q (addition) on the twist E2, evaluated
// at P=(xp,yp) on E1 and embedded into Fp12.  With untwist (x,y) ->
// (x/w^2, y/w^3) the line at P is
//     l = y_p - lam*x_p*w^-1 + (lam*x_T - y_T)*w^-3
// and w^-1 = xi^-1 w^5, w^-3 = xi^-1 w^3.  Scaling by xi (an Fp2 subfield
// factor, killed by the final exponentiation) gives the sparse element
//     l' = (xi*y_p) * 1  +  (lam*x_T - y_T) * w^3  +  (-lam*x_p) * w^5
// with w^3 = v*w and w^5 = v^2*w in our tower basis.
static void line_eval(fp12 &l, const fp2 &lam, const fp2 &xt, const fp2 &yt,
                      const fp &xp, const fp &yp) {
  fp2 c_one;                    // xi * y_p, y_p in Fp
  fp2 xi = {FP_ONE, FP_ONE};    // 1 + u in Montgomery form
  fp2_mul_fp(c_one, xi, yp);
  fp2 c_w3;                     // lam*x_T - y_T
  fp2_mul(c_w3, lam, xt);
  fp2_sub(c_w3, c_w3, yt);
  fp2 c_w5;                     // -lam * x_p
  fp2_mul_fp(c_w5, lam, xp);
  fp2_neg(c_w5, c_w5);
  l.a.a = c_one;
  l.a.b = FP2_ZERO_;
  l.a.c = FP2_ZERO_;
  l.b.a = FP2_ZERO_;
  l.b.b = c_w3;                 // v * w  == w^3
  l.b.c = c_w5;                 // v^2 * w == w^5
}

// miller loop over |x| for P (affine G1) and Q (affine G2); result needs
// final exponentiation.  Neither input may be infinity (callers check).
static void miller_loop_acc(fp12 &facc, const fp &xp, const fp &yp,
                            const fp2 &xq, const fp2 &yq) {
  // computes f_{|x|,Q}(P) into a local accumulator and MULTIPLIES it into
  // facc (the shared multi-pairing product must not be squared per step)
  fp12 f = FP12_ONE_;
  fp2 xt = xq, yt = yq;         // T = Q, affine on E2
  uint64_t n = BLS_ABS_X;
  int top = 63;
  while (!((n >> top) & 1)) top--;
  for (int i = top - 1; i >= 0; i--) {
    // f <- f^2 * l_{T,T}(P) ; T <- 2T
    fp12 sq;
    fp12_sqr(sq, f);
    fp2 num, den, lam, t;
    fp2_sqr(num, xt);
    fp2 three = num;
    fp2_add(three, three, num);
    fp2_add(three, three, num);      // 3 x_T^2
    fp2_add(den, yt, yt);            // 2 y_T
    fp2_inv(t, den);
    fp2_mul(lam, three, t);
    fp12 l;
    line_eval(l, lam, xt, yt, xp, yp);
    fp12_mul(f, sq, l);
    // affine double on E2 (a = 0)
    fp2 x3, y3;
    fp2_sqr(x3, lam);
    fp2_sub(x3, x3, xt);
    fp2_sub(x3, x3, xt);
    fp2_sub(t, xt, x3);
    fp2_mul(y3, lam, t);
    fp2_sub(y3, y3, yt);
    xt = x3;
    yt = y3;
    if ((n >> i) & 1) {
      // f <- f * l_{T,Q}(P) ; T <- T + Q
      fp2 dy, dx, ti;
      fp2_sub(dy, yq, yt);
      fp2_sub(dx, xq, xt);
      fp2_inv(ti, dx);
      fp2_mul(lam, dy, ti);
      fp12 l2;
      line_eval(l2, lam, xt, yt, xp, yp);
      fp12 nf;
      fp12_mul(nf, f, l2);
      f = nf;
      fp2 x3b, y3b;
      fp2_sqr(x3b, lam);
      fp2_sub(x3b, x3b, xt);
      fp2_sub(x3b, x3b, xq);
      fp2_sub(t, xt, x3b);
      fp2_mul(y3b, lam, t);
      fp2_sub(y3b, y3b, yt);
      xt = x3b;
      yt = y3b;
    }
  }
  // x < 0: conjugate (pairing.py:63-64)
  fp12 c;
  fp12_conj(c, f);
  fp12 prod;
  fp12_mul(prod, facc, c);
  facc = prod;
}

static void fp12_pow_x_abs(fp12 &o, const fp12 &g) {
  // g^|x| square-and-multiply (pairing.py:107-109)
  uint64_t n = BLS_ABS_X;
  int top = 63;
  while (!((n >> top) & 1)) top--;
  fp12 acc = g;
  for (int i = top - 1; i >= 0; i--) {
    fp12 s;
    fp12_sqr(s, acc);
    acc = s;
    if ((n >> i) & 1) {
      fp12 m;
      fp12_mul(m, acc, g);
      acc = m;
    }
  }
  o = acc;
}

static void fp12_pow_x(fp12 &o, const fp12 &g) {
  fp12 t;
  fp12_pow_x_abs(t, g);
  fp12_conj(o, t);              // x < 0, cyclotomic inverse == conj
}

static void final_exponentiation(fp12 &o, const fp12 &fin) {
  // pairing.py:117-129
  fp12 f = fin, t, inv, conj;
  fp12_conj(conj, f);
  fp12_inv(inv, f);
  fp12_mul(t, conj, inv);       // f^(p^6 - 1)
  fp12 fr;
  fp12_frobenius(fr, t, 2);
  fp12_mul(f, fr, t);           // ^(p^2 + 1)
  // hard part
  fp12 e1, e2, e3, u, v;
  fp12_pow_x(u, f);
  fp12_conj(v, f);
  fp12_mul(e1, u, v);           // f^(x-1)
  fp12_pow_x(u, e1);
  fp12_conj(v, e1);
  fp12_mul(e1, u, v);           // f^((x-1)^2)
  fp12_pow_x(u, e1);
  fp12_frobenius(v, e1, 1);
  fp12_mul(e2, u, v);           // e1^(x+p)
  fp12_pow_x(u, e2);
  fp12_pow_x(t, u);             // e2^(x^2)
  fp12_frobenius(u, e2, 2);
  fp12_mul(t, t, u);
  fp12_conj(u, e2);
  fp12_mul(e3, t, u);           // e2^(x^2+p^2-1)
  fp12 f2, f3;
  fp12_sqr(f2, f);
  fp12_mul(f3, f2, f);
  fp12_mul(o, e3, f3);
}

// ---------------------------------------------------------------------------
// Hash to curve (RFC 9380; mirrors host/h2c.py)
// ---------------------------------------------------------------------------

// -- SHA-256 (compact, public algorithm) -------------------------------------

struct sha256_ctx {
  uint32_t h[8];
  uint64_t len;
  uint8_t buf[64];
  int off;
};

static const uint32_t SHA_K[64] = {
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2};

static inline uint32_t rotr(uint32_t x, int n) {
  return (x >> n) | (x << (32 - n));
}

static void sha256_init(sha256_ctx &c) {
  static const uint32_t iv[8] = {0x6a09e667, 0xbb67ae85, 0x3c6ef372,
                                 0xa54ff53a, 0x510e527f, 0x9b05688c,
                                 0x1f83d9ab, 0x5be0cd19};
  memcpy(c.h, iv, sizeof iv);
  c.len = 0;
  c.off = 0;
}

static void sha256_block(sha256_ctx &c, const uint8_t *p) {
  uint32_t w[64];
  for (int i = 0; i < 16; i++)
    w[i] = ((uint32_t)p[4 * i] << 24) | ((uint32_t)p[4 * i + 1] << 16) |
           ((uint32_t)p[4 * i + 2] << 8) | p[4 * i + 3];
  for (int i = 16; i < 64; i++) {
    uint32_t s0 = rotr(w[i - 15], 7) ^ rotr(w[i - 15], 18) ^ (w[i - 15] >> 3);
    uint32_t s1 = rotr(w[i - 2], 17) ^ rotr(w[i - 2], 19) ^ (w[i - 2] >> 10);
    w[i] = w[i - 16] + s0 + w[i - 7] + s1;
  }
  uint32_t a = c.h[0], b = c.h[1], cc = c.h[2], d = c.h[3], e = c.h[4],
           f = c.h[5], g = c.h[6], h = c.h[7];
  for (int i = 0; i < 64; i++) {
    uint32_t S1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25);
    uint32_t ch = (e & f) ^ (~e & g);
    uint32_t t1 = h + S1 + ch + SHA_K[i] + w[i];
    uint32_t S0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22);
    uint32_t mj = (a & b) ^ (a & cc) ^ (b & cc);
    uint32_t t2 = S0 + mj;
    h = g; g = f; f = e; e = d + t1;
    d = cc; cc = b; b = a; a = t1 + t2;
  }
  c.h[0] += a; c.h[1] += b; c.h[2] += cc; c.h[3] += d;
  c.h[4] += e; c.h[5] += f; c.h[6] += g; c.h[7] += h;
}

static void sha256_update(sha256_ctx &c, const uint8_t *p, size_t n) {
  c.len += n;
  while (n) {
    size_t take = 64 - c.off;
    if (take > n) take = n;
    memcpy(c.buf + c.off, p, take);
    c.off += take;
    p += take;
    n -= take;
    if (c.off == 64) {
      sha256_block(c, c.buf);
      c.off = 0;
    }
  }
}

static void sha256_final(sha256_ctx &c, uint8_t out[32]) {
  uint64_t bitlen = c.len * 8;
  uint8_t pad = 0x80;
  sha256_update(c, &pad, 1);
  uint8_t zero = 0;
  while (c.off != 56) sha256_update(c, &zero, 1);
  uint8_t lb[8];
  for (int i = 0; i < 8; i++) lb[i] = (uint8_t)(bitlen >> (8 * (7 - i)));
  sha256_update(c, lb, 8);
  for (int i = 0; i < 8; i++) {
    out[4 * i] = (uint8_t)(c.h[i] >> 24);
    out[4 * i + 1] = (uint8_t)(c.h[i] >> 16);
    out[4 * i + 2] = (uint8_t)(c.h[i] >> 8);
    out[4 * i + 3] = (uint8_t)c.h[i];
  }
}

// -- expand_message_xmd (h2c.py:23-36) --------------------------------------

static void expand_message_xmd(uint8_t *out, int len_in_bytes,
                               const uint8_t *msg, int msg_len,
                               const uint8_t *dst, int dst_len) {
  int ell = (len_in_bytes + 31) / 32;
  uint8_t dst_prime[256];
  memcpy(dst_prime, dst, dst_len);
  dst_prime[dst_len] = (uint8_t)dst_len;
  int dpl = dst_len + 1;
  uint8_t z_pad[64] = {0};
  uint8_t lib[2] = {(uint8_t)(len_in_bytes >> 8), (uint8_t)len_in_bytes};
  uint8_t b0[32], bi[32];
  sha256_ctx c;
  sha256_init(c);
  sha256_update(c, z_pad, 64);
  sha256_update(c, msg, msg_len);
  sha256_update(c, lib, 2);
  uint8_t zero = 0;
  sha256_update(c, &zero, 1);
  sha256_update(c, dst_prime, dpl);
  sha256_final(c, b0);
  sha256_init(c);
  sha256_update(c, b0, 32);
  uint8_t one = 1;
  sha256_update(c, &one, 1);
  sha256_update(c, dst_prime, dpl);
  sha256_final(c, bi);
  int written = 0;
  for (int i = 1; i <= ell; i++) {
    int take = len_in_bytes - written;
    if (take > 32) take = 32;
    memcpy(out + written, bi, take);
    written += take;
    if (i == ell) break;
    uint8_t tmp[32];
    for (int j = 0; j < 32; j++) tmp[j] = b0[j] ^ bi[j];
    sha256_init(c);
    sha256_update(c, tmp, 32);
    uint8_t idx = (uint8_t)(i + 1);
    sha256_update(c, &idx, 1);
    sha256_update(c, dst_prime, dpl);
    sha256_final(c, bi);
  }
}

// reduce 64 big-endian bytes mod p -> Montgomery fp.
// 2^512 splitting: v = hi*2^384 + lo ; both in Montgomery via R2 tricks:
//   lo (48B)   -> mont(lo)  = lo * R  = mont_mul(lo, R2)
//   hi (16B)   -> hi * 2^384 mod p = mont_mul(hi, R2) gives hi*R... careful:
// We just do it digit-wise: v mod p with schoolbook: treat as 8 limbs and
// subtract; simplest correct: interpret 512-bit as l[8], then compute
// v mod p via repeated Montgomery trick: v = hi*2^384 + lo;
// mont_mul(hi_as_fp, R2) = hi * R^2 * R^-1 = hi * R = hi * 2^384 mod p. Add
// mont-encoded... we need the RAW value v mod p, then to_mont.  hi*2^384
// mod p: to_mont(hi) IS hi*R = hi*2^384 (mod p) in raw terms.  So:
//   raw(v mod p) = from?  We want mont(v).  mont(v) = v*R mod p
//     = (hi*2^384 + lo)*R = hi*R*2^384 + lo*R = to_mont(to_mont(hi)) + to_mont(lo)
static void fp_from_64bytes(fp &o, const uint8_t *b) {
  uint8_t hi_b[48] = {0}, lo_b[48];
  memcpy(hi_b + 32, b, 16);        // top 16 bytes, right-aligned in 48
  memcpy(lo_b, b + 16, 48);
  // raw loads without range check (values reduced mod p below via to_mont)
  fp hi_raw, lo_raw;
  for (int i = 0; i < 6; i++) {
    uint64_t w1 = 0, w2 = 0;
    for (int j = 0; j < 8; j++) {
      w1 = (w1 << 8) | hi_b[(5 - i) * 8 + j];
      w2 = (w2 << 8) | lo_b[(5 - i) * 8 + j];
    }
    hi_raw.l[i] = w1;
    lo_raw.l[i] = w2;
  }
  // reduce raw values below p by subtracting p a few times (values < 2^384,
  // p ~ 2^381 -> at most 7 subtractions)
  while (geq6(hi_raw.l, BLS_P)) sub6(hi_raw.l, hi_raw.l, BLS_P);
  while (geq6(lo_raw.l, BLS_P)) sub6(lo_raw.l, lo_raw.l, BLS_P);
  fp hi_m, hi_m2, lo_m;
  fp_to_mont(hi_m, hi_raw);
  fp_to_mont(hi_m2, hi_m);         // hi * R^2... = mont(hi * R) = mont(hi*2^384)
  fp_to_mont(lo_m, lo_raw);
  fp_add(o, hi_m2, lo_m);
}

// -- SSWU + isogeny (G1) ----------------------------------------------------

static void load_fp(fp &o, const uint64_t *src) {
  memcpy(o.l, src, 6 * sizeof(uint64_t));
}

static void sswu_g1(fp &xo, fp &yo, const fp &u) {
  fp A, B, Z;
  load_fp(A, SSWU_A1);
  load_fp(B, SSWU_B1);
  load_fp(Z, SSWU_Z1);
  fp u2, tv1, tv2, x1;
  fp_sqr(u2, u);
  fp_mul(tv1, Z, u2);
  fp_sqr(tv2, tv1);
  fp_add(tv2, tv2, tv1);
  if (fp_is_zero(tv2)) {
    fp za, zi;
    fp_mul(za, Z, A);
    fp_inv(zi, za);
    fp_mul(x1, B, zi);
  } else {
    fp nb, ai, ti, one_ti;
    fp_neg(nb, B);
    fp_inv(ai, A);
    fp_inv(ti, tv2);
    fp_add(one_ti, FP_ONE, ti);
    fp_mul(x1, nb, ai);
    fp_mul(x1, x1, one_ti);
  }
  fp gx1, x3, ax;
  fp_sqr(x3, x1);
  fp_mul(x3, x3, x1);
  fp_mul(ax, A, x1);
  fp_add(gx1, x3, ax);
  fp_add(gx1, gx1, B);
  fp x2, gx2;
  fp_mul(x2, tv1, x1);
  fp_sqr(x3, x2);
  fp_mul(x3, x3, x2);
  fp_mul(ax, A, x2);
  fp_add(gx2, x3, ax);
  fp_add(gx2, gx2, B);
  fp x, y;
  if (fp_is_square(gx1)) {
    x = x1;
    fp_sqrt(y, gx1);
  } else {
    x = x2;
    fp_sqrt(y, gx2);
  }
  if (fp_sgn0(u) != fp_sgn0(y)) fp_neg(y, y);
  xo = x;
  yo = y;
}

static void sswu_g2(fp2 &xo, fp2 &yo, const fp2 &u) {
  fp2 A, B, Z;
  load_fp2(A, SSWU_A2);
  load_fp2(B, SSWU_B2);
  load_fp2(Z, SSWU_Z2);
  fp2 u2, tv1, tv2, x1;
  fp2_sqr(u2, u);
  fp2_mul(tv1, Z, u2);
  fp2_sqr(tv2, tv1);
  fp2_add(tv2, tv2, tv1);
  if (fp2_is_zero(tv2)) {
    fp2 za, zi;
    fp2_mul(za, Z, A);
    fp2_inv(zi, za);
    fp2_mul(x1, B, zi);
  } else {
    fp2 nb, ai, ti, one_ti;
    fp2_neg(nb, B);
    fp2_inv(ai, A);
    fp2_inv(ti, tv2);
    fp2_add(one_ti, FP2_ONE_, ti);
    fp2_mul(x1, nb, ai);
    fp2_mul(x1, x1, one_ti);
  }
  fp2 gx1, x3, ax;
  fp2_sqr(x3, x1);
  fp2_mul(x3, x3, x1);
  fp2_mul(ax, A, x1);
  fp2_add(gx1, x3, ax);
  fp2_add(gx1, gx1, B);
  fp2 x2, gx2;
  fp2_mul(x2, tv1, x1);
  fp2_sqr(x3, x2);
  fp2_mul(x3, x3, x2);
  fp2_mul(ax, A, x2);
  fp2_add(gx2, x3, ax);
  fp2_add(gx2, gx2, B);
  fp2 x, y;
  if (fp2_is_square(gx1)) {
    x = x1;
    fp2_sqrt(y, gx1);
  } else {
    x = x2;
    fp2_sqrt(y, gx2);
  }
  if (fp2_sgn0(u) != fp2_sgn0(y)) fp2_neg(y, y);
  xo = x;
  yo = y;
}

// affine add on the iso curves (A != 0); inf flags via pointers
struct afp { fp x, y; int inf; };
struct afp2 { fp2 x, y; int inf; };

static void affine_add_iso_g1(afp &o, const afp &p, const afp &q,
                              const fp &A) {
  if (p.inf) { o = q; return; }
  if (q.inf) { o = p; return; }
  fp lam;
  if (fp_eq(p.x, q.x)) {
    fp ysum;
    fp_add(ysum, p.y, q.y);
    if (fp_is_zero(ysum)) { o.inf = 1; return; }
    fp n, d, di;
    fp_sqr(n, p.x);
    fp three = n;
    fp_add(three, three, n);
    fp_add(three, three, n);
    fp_add(n, three, A);
    fp_add(d, p.y, p.y);
    fp_inv(di, d);
    fp_mul(lam, n, di);
  } else {
    fp n, d, di;
    fp_sub(n, q.y, p.y);
    fp_sub(d, q.x, p.x);
    fp_inv(di, d);
    fp_mul(lam, n, di);
  }
  fp x3, y3, t;
  fp_sqr(x3, lam);
  fp_sub(x3, x3, p.x);
  fp_sub(x3, x3, q.x);
  fp_sub(t, p.x, x3);
  fp_mul(y3, lam, t);
  fp_sub(y3, y3, p.y);
  o.x = x3;
  o.y = y3;
  o.inf = 0;
}

static void affine_add_iso_g2(afp2 &o, const afp2 &p, const afp2 &q,
                              const fp2 &A) {
  if (p.inf) { o = q; return; }
  if (q.inf) { o = p; return; }
  fp2 lam;
  if (fp2_eq(p.x, q.x)) {
    fp2 ysum;
    fp2_add(ysum, p.y, q.y);
    if (fp2_is_zero(ysum)) { o.inf = 1; return; }
    fp2 n, d, di;
    fp2_sqr(n, p.x);
    fp2 three = n;
    fp2_add(three, three, n);
    fp2_add(three, three, n);
    fp2_add(n, three, A);
    fp2_add(d, p.y, p.y);
    fp2_inv(di, d);
    fp2_mul(lam, n, di);
  } else {
    fp2 n, d, di;
    fp2_sub(n, q.y, p.y);
    fp2_sub(d, q.x, p.x);
    fp2_inv(di, d);
    fp2_mul(lam, n, di);
  }
  fp2 x3, y3, t;
  fp2_sqr(x3, lam);
  fp2_sub(x3, x3, p.x);
  fp2_sub(x3, x3, q.x);
  fp2_sub(t, p.x, x3);
  fp2_mul(y3, lam, t);
  fp2_sub(y3, y3, p.y);
  o.x = x3;
  o.y = y3;
  o.inf = 0;
}

static void horner_fp(fp &o, const uint64_t *coeffs, int n, const fp &x) {
  fp acc = FP_ZERO;
  for (int i = n - 1; i >= 0; i--) {
    fp c, t;
    load_fp(c, coeffs + 6 * i);
    fp_mul(t, acc, x);
    fp_add(acc, t, c);
  }
  o = acc;
}

static void horner_fp2(fp2 &o, const uint64_t *coeffs, int n, const fp2 &x) {
  fp2 acc = FP2_ZERO_;
  for (int i = n - 1; i >= 0; i--) {
    fp2 c, t;
    load_fp2(c, coeffs + 12 * i);
    fp2_mul(t, acc, x);
    fp2_add(acc, t, c);
  }
  o = acc;
}

// psi endomorphism for G2 cofactor clearing (host/curve.py:176-196)
static void g2_psi_affine(fp2 &xo, fp2 &yo, const fp2 &x, const fp2 &y) {
  fp2 cx, cy, t;
  load_fp2(cx, PSI_CX);
  load_fp2(cy, PSI_CY);
  fp2_conj(t, x);
  fp2_mul(xo, cx, t);
  fp2_conj(t, y);
  fp2_mul(yo, cy, t);
}

static void g2_psi_jac(g2p &o, const g2p &p) {
  if (g2_is_inf(p)) { o = G2_INF; return; }
  fp2 x, y;
  int inf;
  g2_affine(x, y, inf, p);
  fp2 xo, yo;
  g2_psi_affine(xo, yo, x, y);
  g2_from_affine(o, xo, yo);
}

// full hash-to-curve G1 (h2c.py:255-263)
static int hash_to_g1(g1p &out, const uint8_t *msg, int msg_len,
                      const uint8_t *dst, int dst_len) {
  uint8_t ub[128];
  expand_message_xmd(ub, 128, msg, msg_len, dst, dst_len);
  fp u0, u1;
  fp_from_64bytes(u0, ub);
  fp_from_64bytes(u1, ub + 64);
  afp q0, q1, r;
  q0.inf = q1.inf = 0;
  sswu_g1(q0.x, q0.y, u0);
  sswu_g1(q1.x, q1.y, u1);
  fp A;
  load_fp(A, SSWU_A1);
  affine_add_iso_g1(r, q0, q1, A);
  if (r.inf) { out = G1_INF; return 1; }
  // 11-isogeny to E1
  fp xn, xd, yn, yd, xdi, ydi, xo, yo, t;
  horner_fp(xn, G1_ISO_XN, G1_ISO_XN_LEN, r.x);
  horner_fp(xd, G1_ISO_XD, G1_ISO_XD_LEN, r.x);
  horner_fp(yn, G1_ISO_YN, G1_ISO_YN_LEN, r.x);
  horner_fp(yd, G1_ISO_YD, G1_ISO_YD_LEN, r.x);
  fp_inv(xdi, xd);
  fp_mul(xo, xn, xdi);
  fp_inv(ydi, yd);
  fp_mul(t, yn, ydi);
  fp_mul(yo, r.y, t);
  g1p p;
  g1_from_affine(p, xo, yo);
  // clear cofactor: mul by h_eff = 1 - x  (curve.py:163-165)
  g1_mul(out, p, G1_HEFF, 1);
  return 1;
}

// full hash-to-curve G2 (h2c.py:212-220)
static int hash_to_g2(g2p &out, const uint8_t *msg, int msg_len,
                      const uint8_t *dst, int dst_len) {
  uint8_t ub[256];
  expand_message_xmd(ub, 256, msg, msg_len, dst, dst_len);
  fp2 u0, u1;
  fp_from_64bytes(u0.c0, ub);
  fp_from_64bytes(u0.c1, ub + 64);
  fp_from_64bytes(u1.c0, ub + 128);
  fp_from_64bytes(u1.c1, ub + 192);
  afp2 q0, q1, r;
  q0.inf = q1.inf = 0;
  sswu_g2(q0.x, q0.y, u0);
  sswu_g2(q1.x, q1.y, u1);
  fp2 A;
  load_fp2(A, SSWU_A2);
  affine_add_iso_g2(r, q0, q1, A);
  if (r.inf) { out = G2_INF; return 1; }
  // 3-isogeny to E2
  fp2 xn, xd, yn, yd, xdi, ydi, xo, yo, t;
  horner_fp2(xn, G2_ISO_XN, G2_ISO_XN_LEN, r.x);
  horner_fp2(xd, G2_ISO_XD, G2_ISO_XD_LEN, r.x);
  horner_fp2(yn, G2_ISO_YN, G2_ISO_YN_LEN, r.x);
  horner_fp2(yd, G2_ISO_YD, G2_ISO_YD_LEN, r.x);
  fp2_inv(xdi, xd);
  fp2_mul(xo, xn, xdi);
  fp2_inv(ydi, yd);
  fp2_mul(t, yn, ydi);
  fp2_mul(yo, r.y, t);
  g2p p;
  g2_from_affine(p, xo, yo);
  // clear cofactor: [x^2-x-1]P + [x-1]psi(P) + psi(psi(2P))
  // (curve.py:183-196; X negative handled via negate-after-mul)
  g2p xP, x2P, tjp, u, v, acc;
  g2_mul(xP, p, &BLS_ABS_X, 1);
  g2_neg(xP, xP);                 // x*P, x < 0
  g2_mul(x2P, xP, &BLS_ABS_X, 1);
  g2_neg(x2P, x2P);               // x^2*P
  g2p negxP, negP;
  g2_neg(negxP, xP);
  g2_neg(negP, p);
  g2_add(tjp, x2P, negxP);        // (x^2 - x) P
  g2_add(tjp, tjp, negP);         // (x^2 - x - 1) P
  g2_add(u, xP, negP);            // (x - 1) P
  g2_psi_jac(u, u);
  g2_add(acc, tjp, u);
  g2p twoP;
  g2_double(twoP, p);
  g2_psi_jac(v, twoP);
  g2_psi_jac(v, v);
  g2_add(out, acc, v);
  return 1;
}

// ---------------------------------------------------------------------------
// C ABI
// ---------------------------------------------------------------------------

static void load_scalar(uint64_t *k, const uint8_t *be32) {
  for (int i = 0; i < 4; i++) {
    uint64_t w = 0;
    for (int j = 0; j < 8; j++) w = (w << 8) | be32[(3 - i) * 8 + j];
    k[i] = w;
  }
}

extern "C" {

int ntv_version(void) { return 1; }

// -- group ops (compressed bytes in/out; return 0 on success) ---------------

int ntv_g1_base_mul(const uint8_t sk[32], uint8_t out[48]) {
  uint64_t k[4];
  load_scalar(k, sk);
  g1p g, r;
  fp gx, gy;
  load_fp(gx, G1_GEN_X);
  load_fp(gy, G1_GEN_Y);
  g1_from_affine(g, gx, gy);
  g1_mul(r, g, k, 4);
  g1_compress(out, r);
  return 0;
}

int ntv_g2_base_mul(const uint8_t sk[32], uint8_t out[96]) {
  uint64_t k[4];
  load_scalar(k, sk);
  g2p g, r;
  fp2 gx, gy;
  load_fp2(gx, G2_GEN_X);
  load_fp2(gy, G2_GEN_Y);
  g2_from_affine(g, gx, gy);
  g2_mul(r, g, k, 4);
  g2_compress(out, r);
  return 0;
}

int ntv_g1_mul(const uint8_t p[48], const uint8_t sk[32], uint8_t out[48]) {
  g1p pt, r;
  if (!g1_decompress(pt, p, 0)) return 1;
  uint64_t k[4];
  load_scalar(k, sk);
  g1_mul(r, pt, k, 4);
  g1_compress(out, r);
  return 0;
}

int ntv_g2_mul(const uint8_t p[96], const uint8_t sk[32], uint8_t out[96]) {
  g2p pt, r;
  if (!g2_decompress(pt, p, 0)) return 1;
  uint64_t k[4];
  load_scalar(k, sk);
  g2_mul(r, pt, k, 4);
  g2_compress(out, r);
  return 0;
}

int ntv_g1_add(const uint8_t a[48], const uint8_t b[48], uint8_t out[48]) {
  g1p pa, pb, r;
  if (!g1_decompress(pa, a, 0) || !g1_decompress(pb, b, 0)) return 1;
  g1_add(r, pa, pb);
  g1_compress(out, r);
  return 0;
}

int ntv_g2_add(const uint8_t a[96], const uint8_t b[96], uint8_t out[96]) {
  g2p pa, pb, r;
  if (!g2_decompress(pa, a, 0) || !g2_decompress(pb, b, 0)) return 1;
  g2_add(r, pa, pb);
  g2_compress(out, r);
  return 0;
}

// multi-scalar mul: pts = n*48 (or 96) bytes, scalars = n*32 bytes
int ntv_g1_msm(const uint8_t *pts, const uint8_t *scalars, int n,
               uint8_t out[48]) {
  g1p acc = G1_INF;
  for (int i = 0; i < n; i++) {
    g1p pt, m;
    if (!g1_decompress(pt, pts + 48 * i, 0)) return 1;
    uint64_t k[4];
    load_scalar(k, scalars + 32 * i);
    g1_mul(m, pt, k, 4);
    g1_add(acc, acc, m);
  }
  g1_compress(out, acc);
  return 0;
}

int ntv_g2_msm(const uint8_t *pts, const uint8_t *scalars, int n,
               uint8_t out[96]) {
  g2p acc = G2_INF;
  for (int i = 0; i < n; i++) {
    g2p pt, m;
    if (!g2_decompress(pt, pts + 96 * i, 0)) return 1;
    uint64_t k[4];
    load_scalar(k, scalars + 32 * i);
    g2_mul(m, pt, k, 4);
    g2_add(acc, acc, m);
  }
  g2_compress(out, acc);
  return 0;
}

int ntv_g1_validate(const uint8_t p[48]) {
  g1p pt;
  return g1_decompress(pt, p, 1) ? 0 : 1;
}

int ntv_g2_validate(const uint8_t p[96]) {
  g2p pt;
  return g2_decompress(pt, p, 1) ? 0 : 1;
}

// -- hash to curve / sign ----------------------------------------------------

int ntv_hash_to_g1(const uint8_t *msg, int msg_len, const uint8_t *dst,
                   int dst_len, uint8_t out[48]) {
  g1p r;
  if (!hash_to_g1(r, msg, msg_len, dst, dst_len)) return 1;
  g1_compress(out, r);
  return 0;
}

int ntv_hash_to_g2(const uint8_t *msg, int msg_len, const uint8_t *dst,
                   int dst_len, uint8_t out[96]) {
  g2p r;
  if (!hash_to_g2(r, msg, msg_len, dst, dst_len)) return 1;
  g2_compress(out, r);
  return 0;
}

int ntv_sign_g1(const uint8_t sk[32], const uint8_t *msg, int msg_len,
                const uint8_t *dst, int dst_len, uint8_t out[48]) {
  g1p h, r;
  if (!hash_to_g1(h, msg, msg_len, dst, dst_len)) return 1;
  uint64_t k[4];
  load_scalar(k, sk);
  g1_mul(r, h, k, 4);
  g1_compress(out, r);
  return 0;
}

int ntv_sign_g2(const uint8_t sk[32], const uint8_t *msg, int msg_len,
                const uint8_t *dst, int dst_len, uint8_t out[96]) {
  g2p h, r;
  if (!hash_to_g2(h, msg, msg_len, dst, dst_len)) return 1;
  uint64_t k[4];
  load_scalar(k, sk);
  g2_mul(r, h, k, 4);
  g2_compress(out, r);
  return 0;
}

// -- pairing -----------------------------------------------------------------

// prod_i e(P_i, Q_i) == 1 ?  g1s = n*48, g2s = n*96 compressed.
// returns 1 when the check holds, 0 when it fails, <0 on decode error.
int ntv_pairing_check(const uint8_t *g1s, const uint8_t *g2s, int n,
                      int check_subgroups) {
  fp12 f = FP12_ONE_;
  for (int i = 0; i < n; i++) {
    g1p p;
    g2p q;
    if (!g1_decompress(p, g1s + 48 * i, check_subgroups)) return -1;
    if (!g2_decompress(q, g2s + 96 * i, check_subgroups)) return -2;
    if (g1_is_inf(p) || g2_is_inf(q)) continue;   // e(0, Q) = 1
    fp xp, yp;
    fp2 xq, yq;
    int inf;
    g1_affine(xp, yp, inf, p);
    g2_affine(xq, yq, inf, q);
    miller_loop_acc(f, xp, yp, xq, yq);
  }
  fp12 e;
  final_exponentiation(e, f);
  return fp12_is_one(e) ? 1 : 0;
}

// BLS verify with pk on G1 (sigs on G2):  e(pk, H(m)) == e(g1, sig)
//   <=> e(-g1, sig) * e(pk, H(m)) == 1
int ntv_verify_g2sig(const uint8_t pk[48], const uint8_t *msg, int msg_len,
                     const uint8_t *dst, int dst_len, const uint8_t sig[96]) {
  g1p pkp, negg;
  g2p sp, h;
  if (!g1_decompress(pkp, pk, 1)) return -1;
  if (!g2_decompress(sp, sig, 1)) return -2;
  if (!hash_to_g2(h, msg, msg_len, dst, dst_len)) return -3;
  if (g1_is_inf(pkp) || g2_is_inf(sp)) return 0;
  fp gx, gy;
  load_fp(gx, G1_GEN_X);
  load_fp(gy, G1_GEN_Y);
  g1p g;
  g1_from_affine(g, gx, gy);
  g1_neg(negg, g);
  fp12 f = FP12_ONE_;
  fp xp, yp;
  fp2 xq, yq;
  int inf;
  g1_affine(xp, yp, inf, pkp);
  g2_affine(xq, yq, inf, h);
  miller_loop_acc(f, xp, yp, xq, yq);
  g1_affine(xp, yp, inf, negg);
  g2_affine(xq, yq, inf, sp);
  miller_loop_acc(f, xp, yp, xq, yq);
  fp12 e;
  final_exponentiation(e, f);
  return fp12_is_one(e) ? 1 : 0;
}

// BLS verify with pk on G2 (sigs on G1):  e(H(m), pk) == e(sig, g2)
//   <=> e(H(m), pk) * e(-sig, g2) == 1
int ntv_verify_g1sig(const uint8_t pk[96], const uint8_t *msg, int msg_len,
                     const uint8_t *dst, int dst_len, const uint8_t sig[48]) {
  g2p pkp, g;
  g1p sp, negs;
  g1p h;
  if (!g2_decompress(pkp, pk, 1)) return -1;
  if (!g1_decompress(sp, sig, 1)) return -2;
  if (!hash_to_g1(h, msg, msg_len, dst, dst_len)) return -3;
  if (g2_is_inf(pkp) || g1_is_inf(sp)) return 0;
  fp2 gx, gy;
  load_fp2(gx, G2_GEN_X);
  load_fp2(gy, G2_GEN_Y);
  g2_from_affine(g, gx, gy);
  g1_neg(negs, sp);
  fp12 f = FP12_ONE_;
  fp xp, yp;
  fp2 xq, yq;
  int inf;
  g1_affine(xp, yp, inf, h);
  g2_affine(xq, yq, inf, pkp);
  miller_loop_acc(f, xp, yp, xq, yq);
  g1_affine(xp, yp, inf, negs);
  g2_affine(xq, yq, inf, g);
  miller_loop_acc(f, xp, yp, xq, yq);
  fp12 e;
  final_exponentiation(e, f);
  return fp12_is_one(e) ? 1 : 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Debug surface (test-only): raw fp12 IO as 12 x 48-byte big-endian values
// in the Python tower order c0..c5 over Fp2 pairs -> ((c0,c2,c4),(c1,c3,c5)).
// ---------------------------------------------------------------------------

extern "C" {

static void fp12_to_bytes_dbg(uint8_t *out, const fp12 &x) {
  const fp2 *cs[6] = {&x.a.a, &x.b.a, &x.a.b, &x.b.b, &x.a.c, &x.b.c};
  for (int i = 0; i < 6; i++) {
    fp_to_bytes(out + 96 * i, cs[i]->c0);
    fp_to_bytes(out + 96 * i + 48, cs[i]->c1);
  }
}

static int fp12_from_bytes_dbg(fp12 &x, const uint8_t *in) {
  fp2 *cs[6] = {&x.a.a, &x.b.a, &x.a.b, &x.b.b, &x.a.c, &x.b.c};
  for (int i = 0; i < 6; i++) {
    if (!fp_from_bytes(cs[i]->c0, in + 96 * i)) return 0;
    if (!fp_from_bytes(cs[i]->c1, in + 96 * i + 48)) return 0;
  }
  return 1;
}

int ntv_dbg_miller(const uint8_t p[48], const uint8_t q[96],
                   uint8_t out[576]) {
  g1p pp;
  g2p qq;
  if (!g1_decompress(pp, p, 0) || !g2_decompress(qq, q, 0)) return 1;
  fp xp, yp;
  fp2 xq, yq;
  int inf;
  g1_affine(xp, yp, inf, pp);
  g2_affine(xq, yq, inf, qq);
  fp12 f = FP12_ONE_;
  miller_loop_acc(f, xp, yp, xq, yq);
  fp12_to_bytes_dbg(out, f);
  return 0;
}

int ntv_dbg_final_exp(const uint8_t in[576], uint8_t out[576]) {
  fp12 x, e;
  if (!fp12_from_bytes_dbg(x, in)) return 1;
  final_exponentiation(e, x);
  fp12_to_bytes_dbg(out, e);
  return 0;
}

int ntv_dbg_fp12_mul(const uint8_t a[576], const uint8_t b[576],
                     uint8_t out[576]) {
  fp12 x, y, z;
  if (!fp12_from_bytes_dbg(x, a) || !fp12_from_bytes_dbg(y, b)) return 1;
  fp12_mul(z, x, y);
  fp12_to_bytes_dbg(out, z);
  return 0;
}

int ntv_dbg_frobenius(const uint8_t a[576], int j, uint8_t out[576]) {
  fp12 x, z;
  if (!fp12_from_bytes_dbg(x, a)) return 1;
  fp12_frobenius(z, x, j);
  fp12_to_bytes_dbg(out, z);
  return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Uncompressed-affine C ABI: points as raw big-endian affine coordinates
// (G1: x||y 96 bytes; G2: x.c0||x.c1||y.c0||y.c1 192 bytes), all-zero =
// infinity.  No square roots on either side of the boundary — the Python
// wrapper converts int tuples to bytes directly (host/native.py).
// ---------------------------------------------------------------------------

extern "C" {

static int g1_from_aff(g1p &o, const uint8_t *b) {
  int zero = 1;
  for (int i = 0; i < 96; i++) zero &= (b[i] == 0);
  if (zero) { o = G1_INF; return 1; }
  fp x, y;
  if (!fp_from_bytes(x, b) || !fp_from_bytes(y, b + 48)) return 0;
  if (!g1_on_curve(x, y)) return 0;
  g1_from_affine(o, x, y);
  return 1;
}

static void g1_to_aff(uint8_t *b, const g1p &p) {
  if (g1_is_inf(p)) { memset(b, 0, 96); return; }
  fp x, y;
  int inf;
  g1_affine(x, y, inf, p);
  fp_to_bytes(b, x);
  fp_to_bytes(b + 48, y);
}

static int g2_from_aff(g2p &o, const uint8_t *b) {
  int zero = 1;
  for (int i = 0; i < 192; i++) zero &= (b[i] == 0);
  if (zero) { o = G2_INF; return 1; }
  fp2 x, y;
  if (!fp_from_bytes(x.c0, b) || !fp_from_bytes(x.c1, b + 48)) return 0;
  if (!fp_from_bytes(y.c0, b + 96) || !fp_from_bytes(y.c1, b + 144)) return 0;
  if (!g2_on_curve(x, y)) return 0;
  g2_from_affine(o, x, y);
  return 1;
}

static void g2_to_aff(uint8_t *b, const g2p &p) {
  if (g2_is_inf(p)) { memset(b, 0, 192); return; }
  fp2 x, y;
  int inf;
  g2_affine(x, y, inf, p);
  fp_to_bytes(b, x.c0);
  fp_to_bytes(b + 48, x.c1);
  fp_to_bytes(b + 96, y.c0);
  fp_to_bytes(b + 144, y.c1);
}

int ntv_g1_mul_aff(const uint8_t p[96], const uint8_t sk[32],
                   uint8_t out[96]) {
  g1p pt, r;
  if (!g1_from_aff(pt, p)) return 1;
  uint64_t k[4];
  load_scalar(k, sk);
  g1_mul(r, pt, k, 4);
  g1_to_aff(out, r);
  return 0;
}

int ntv_g2_mul_aff(const uint8_t p[192], const uint8_t sk[32],
                   uint8_t out[192]) {
  g2p pt, r;
  if (!g2_from_aff(pt, p)) return 1;
  uint64_t k[4];
  load_scalar(k, sk);
  g2_mul(r, pt, k, 4);
  g2_to_aff(out, r);
  return 0;
}

int ntv_g1_add_aff(const uint8_t a[96], const uint8_t b[96],
                   uint8_t out[96]) {
  g1p pa, pb, r;
  if (!g1_from_aff(pa, a) || !g1_from_aff(pb, b)) return 1;
  g1_add(r, pa, pb);
  g1_to_aff(out, r);
  return 0;
}

int ntv_g2_add_aff(const uint8_t a[192], const uint8_t b[192],
                   uint8_t out[192]) {
  g2p pa, pb, r;
  if (!g2_from_aff(pa, a) || !g2_from_aff(pb, b)) return 1;
  g2_add(r, pa, pb);
  g2_to_aff(out, r);
  return 0;
}

int ntv_g1_msm_aff(const uint8_t *pts, const uint8_t *scalars, int n,
                   uint8_t out[96]) {
  g1p acc = G1_INF;
  for (int i = 0; i < n; i++) {
    g1p pt, m;
    if (!g1_from_aff(pt, pts + 96 * i)) return 1;
    uint64_t k[4];
    load_scalar(k, scalars + 32 * i);
    g1_mul(m, pt, k, 4);
    g1_add(acc, acc, m);
  }
  g1_to_aff(out, acc);
  return 0;
}

int ntv_g2_msm_aff(const uint8_t *pts, const uint8_t *scalars, int n,
                   uint8_t out[192]) {
  g2p acc = G2_INF;
  for (int i = 0; i < n; i++) {
    g2p pt, m;
    if (!g2_from_aff(pt, pts + 192 * i)) return 1;
    uint64_t k[4];
    load_scalar(k, scalars + 32 * i);
    g2_mul(m, pt, k, 4);
    g2_add(acc, acc, m);
  }
  g2_to_aff(out, acc);
  return 0;
}

int ntv_hash_to_g1_aff(const uint8_t *msg, int msg_len, const uint8_t *dst,
                       int dst_len, uint8_t out[96]) {
  g1p r;
  if (!hash_to_g1(r, msg, msg_len, dst, dst_len)) return 1;
  g1_to_aff(out, r);
  return 0;
}

int ntv_hash_to_g2_aff(const uint8_t *msg, int msg_len, const uint8_t *dst,
                       int dst_len, uint8_t out[192]) {
  g2p r;
  if (!hash_to_g2(r, msg, msg_len, dst, dst_len)) return 1;
  g2_to_aff(out, r);
  return 0;
}

// verify with an UNCOMPRESSED pk (callers hold the pk as a point already;
// signature arrives in wire form and is decompressed + subgroup checked)
int ntv_verify_g2sig_affpk(const uint8_t pk[96], const uint8_t *msg,
                           int msg_len, const uint8_t *dst, int dst_len,
                           const uint8_t sig[96]) {
  g1p pkp;
  if (!g1_from_aff(pkp, pk)) return -1;
  g2p sp, h;
  if (!g2_decompress(sp, sig, 1)) return -2;
  if (!hash_to_g2(h, msg, msg_len, dst, dst_len)) return -3;
  if (g1_is_inf(pkp) || g2_is_inf(sp)) return 0;
  fp gx, gy;
  load_fp(gx, G1_GEN_X);
  load_fp(gy, G1_GEN_Y);
  g1p g, negg;
  g1_from_affine(g, gx, gy);
  g1_neg(negg, g);
  fp12 f = FP12_ONE_;
  fp xp, yp;
  fp2 xq, yq;
  int inf;
  g1_affine(xp, yp, inf, pkp);
  g2_affine(xq, yq, inf, h);
  miller_loop_acc(f, xp, yp, xq, yq);
  g1_affine(xp, yp, inf, negg);
  g2_affine(xq, yq, inf, sp);
  miller_loop_acc(f, xp, yp, xq, yq);
  fp12 e;
  final_exponentiation(e, f);
  return fp12_is_one(e) ? 1 : 0;
}

int ntv_verify_g1sig_affpk(const uint8_t pk[192], const uint8_t *msg,
                           int msg_len, const uint8_t *dst, int dst_len,
                           const uint8_t sig[48]) {
  g2p pkp;
  if (!g2_from_aff(pkp, pk)) return -1;
  g1p sp, negs, h;
  if (!g1_decompress(sp, sig, 1)) return -2;
  if (!hash_to_g1(h, msg, msg_len, dst, dst_len)) return -3;
  if (g2_is_inf(pkp) || g1_is_inf(sp)) return 0;
  fp2 gx, gy;
  load_fp2(gx, G2_GEN_X);
  load_fp2(gy, G2_GEN_Y);
  g2p g;
  g2_from_affine(g, gx, gy);
  g1_neg(negs, sp);
  fp12 f = FP12_ONE_;
  fp xp, yp;
  fp2 xq, yq;
  int inf;
  g1_affine(xp, yp, inf, h);
  g2_affine(xq, yq, inf, pkp);
  miller_loop_acc(f, xp, yp, xq, yq);
  g1_affine(xp, yp, inf, negs);
  g2_affine(xq, yq, inf, g);
  miller_loop_acc(f, xp, yp, xq, yq);
  fp12 e;
  final_exponentiation(e, f);
  return fp12_is_one(e) ? 1 : 0;
}

}  // extern "C"

extern "C" {

int ntv_g1_in_subgroup_aff(const uint8_t p[96]) {
  g1p pt;
  if (!g1_from_aff(pt, p)) return -1;
  return g1_in_subgroup(pt) ? 1 : 0;
}

int ntv_g2_in_subgroup_aff(const uint8_t p[192]) {
  g2p pt;
  if (!g2_from_aff(pt, p)) return -1;
  return g2_in_subgroup(pt) ? 1 : 0;
}

}  // extern "C"

extern "C" {

// wire-form decompression to raw affine (used by the batch verifier's host
// packing: Python-side sqrt per signature was the hot spot)
int ntv_g1_decompress_aff(const uint8_t comp[48], int check_subgroup,
                          uint8_t out[96]) {
  g1p p;
  if (!g1_decompress(p, comp, check_subgroup)) return 1;
  g1_to_aff(out, p);
  return 0;
}

int ntv_g2_decompress_aff(const uint8_t comp[96], int check_subgroup,
                          uint8_t out[192]) {
  g2p p;
  if (!g2_decompress(p, comp, check_subgroup)) return 1;
  g2_to_aff(out, p);
  return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Batch packing exports for the TPU pipelines (drand_tpu/crypto/batch.py).
//
// Limb format: per Fp, 24 uint32 base-2^16 little-endian limbs of the
// MONTGOMERY representative (R = 2^384) — byte-identical to the device
// engine's layout (ops/limbs.py), so these arrays feed the jitted pipelines
// with no host-side bigint work at all.  Threaded over the batch.
// ---------------------------------------------------------------------------

#include <thread>

static void fp_to_limbs24_mont(uint32_t *o, const fp &m) {
  for (int i = 0; i < 6; i++) {
    uint64_t w = m.l[i];
    o[4 * i + 0] = (uint32_t)(w & 0xffff);
    o[4 * i + 1] = (uint32_t)((w >> 16) & 0xffff);
    o[4 * i + 2] = (uint32_t)((w >> 32) & 0xffff);
    o[4 * i + 3] = (uint32_t)((w >> 48) & 0xffff);
  }
}

template <typename F>
static void run_batch(int n, int nthreads, F f) {
  if (nthreads <= 0) {
    unsigned hc = std::thread::hardware_concurrency();
    nthreads = hc ? (int)hc : 1;
  }
  if (nthreads > 8) nthreads = 8;   // ts[] capacity
  if (nthreads <= 1 || n < 64) {
    f(0, n);
    return;
  }
  std::thread ts[8];
  int per = (n + nthreads - 1) / nthreads;
  int t = 0;
  for (int lo = 0; lo < n; lo += per, t++) {
    int hi = lo + per > n ? n : lo + per;
    ts[t] = std::thread(f, lo, hi);
  }
  for (int i = 0; i < t; i++) ts[i].join();
}

extern "C" {

// comp: n*48 bytes -> out: n*2*24 u32 Montgomery limbs (x, y); ok[i] in {0,1}
// (failure or infinity -> 0 with zeroed slot).  No subgroup check (the
// device pipeline performs it batched).
int ntv_g1_decompress_limbs_batch(int n, const uint8_t *comp, uint32_t *out,
                                  uint8_t *ok, int nthreads) {
  run_batch(n, nthreads, [&](int lo, int hi) {
    for (int i = lo; i < hi; i++) {
      g1p pt;
      uint32_t *o = out + (size_t)i * 48;
      if (!g1_decompress(pt, comp + (size_t)48 * i, 0) || g1_is_inf(pt)) {
        memset(o, 0, 48 * sizeof(uint32_t));
        ok[i] = 0;
        continue;
      }
      fp_to_limbs24_mont(o, pt.x);        // decompress emits z = 1
      fp_to_limbs24_mont(o + 24, pt.y);
      ok[i] = 1;
    }
  });
  return 0;
}

// comp: n*96 bytes -> out: n*4*24 u32 limbs (x0, x1, y0, y1)
int ntv_g2_decompress_limbs_batch(int n, const uint8_t *comp, uint32_t *out,
                                  uint8_t *ok, int nthreads) {
  run_batch(n, nthreads, [&](int lo, int hi) {
    for (int i = lo; i < hi; i++) {
      g2p pt;
      uint32_t *o = out + (size_t)i * 96;
      if (!g2_decompress(pt, comp + (size_t)96 * i, 0) || g2_is_inf(pt)) {
        memset(o, 0, 96 * sizeof(uint32_t));
        ok[i] = 0;
        continue;
      }
      fp_to_limbs24_mont(o, pt.x.c0);
      fp_to_limbs24_mont(o + 24, pt.x.c1);
      fp_to_limbs24_mont(o + 48, pt.y.c0);
      fp_to_limbs24_mont(o + 72, pt.y.c1);
      ok[i] = 1;
    }
  });
  return 0;
}

// RFC 9380 hash_to_field with count=2 over Fp (h2c.py:39-41):
// msgs: n*msg_len -> out: n*2*24 limbs (u0, u1)
int ntv_h2f_fp_limbs_batch(int n, const uint8_t *msgs, int msg_len,
                           const uint8_t *dst, int dst_len, uint32_t *out,
                           int nthreads) {
  run_batch(n, nthreads, [&](int lo, int hi) {
    uint8_t buf[128];
    for (int i = lo; i < hi; i++) {
      expand_message_xmd(buf, 128, msgs + (size_t)i * msg_len, msg_len,
                         dst, dst_len);
      fp u0, u1;
      fp_from_64bytes(u0, buf);
      fp_from_64bytes(u1, buf + 64);
      fp_to_limbs24_mont(out + (size_t)i * 48, u0);
      fp_to_limbs24_mont(out + (size_t)i * 48 + 24, u1);
    }
  });
  return 0;
}

// count=2 over Fp2 (h2c.py:44-52): out: n*4*24 limbs (u0.c0, u0.c1, u1.c0, u1.c1)
int ntv_h2f_fp2_limbs_batch(int n, const uint8_t *msgs, int msg_len,
                            const uint8_t *dst, int dst_len, uint32_t *out,
                            int nthreads) {
  run_batch(n, nthreads, [&](int lo, int hi) {
    uint8_t buf[256];
    for (int i = lo; i < hi; i++) {
      expand_message_xmd(buf, 256, msgs + (size_t)i * msg_len, msg_len,
                         dst, dst_len);
      fp e[4];
      for (int j = 0; j < 4; j++) fp_from_64bytes(e[j], buf + 64 * j);
      for (int j = 0; j < 4; j++)
        fp_to_limbs24_mont(out + (size_t)i * 96 + 24 * j, e[j]);
    }
  });
  return 0;
}

}  // extern "C"
