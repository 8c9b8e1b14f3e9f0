"""The plain reference: BLS12-381 sign and verify on the host.

A copy of the C++ library is kept beside the benchmark
(`benchmark/reference/`), so that a change to the program cannot change
the yardstick.  It is built once per checkout into `.bench_cache/` with
g++, and loaded through ctypes.  Nothing here imports the program.

Beacon messages follow drand's schemes (crypto/schemes.go):
unchained `sha256(round_be8)`, chained `sha256(prev_sig || round_be8)`.
The library's constants were generated once from the program's host
crypto; what makes them a yardstick is `reference/anchors.json`, the
published answers (RFC 9380 vectors, the curve's generators, beacons the
League of Entropy signed) that `check.anchor_mismatch` holds this
library to in every run.
"""

import ctypes
import hashlib
import os
import subprocess
import threading
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
SRC_DIR = os.path.join(os.path.dirname(HERE), "reference")
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
BUILD_DIR = os.path.join(CHECKOUT, ".bench_cache")

DST_G1 = b"BLS_SIG_BLS12381G1_XMD:SHA-256_SSWU_RO_NUL_"
DST_G2 = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_NUL_"
R_ORDER = 0x73EDA753299D7D483339D80809D1D80553BDA402FFFE5BFEFFFFFFFF00000001

SCHEMES = {
    # scheme id: (signature group, chained, DST) — drand crypto/schemes.go
    "pedersen-bls-chained": ("G2", True, DST_G2),
    "pedersen-bls-unchained": ("G2", False, DST_G2),
    "bls-unchained-on-g1": ("G1", False, DST_G2),
    "bls-unchained-g1-rfc9380": ("G1", False, DST_G1),
}

_LIB = None
_LOCK = threading.Lock()


def _source_digest() -> str:
    h = hashlib.sha256()
    for name in ("bls12381.cc", "constants_gen.h"):
        with open(os.path.join(SRC_DIR, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def lib() -> ctypes.CDLL:
    """Build (once per source digest) and load the reference library."""
    global _LIB
    with _LOCK:
        if _LIB is not None:
            return _LIB
        os.makedirs(BUILD_DIR, exist_ok=True)
        so = os.path.join(BUILD_DIR, f"libbls_ref_{_source_digest()}.so")
        if not os.path.exists(so):
            tmp = f"{so}.part"
            subprocess.run(
                ["g++", "-O3", "-fPIC", "-fno-exceptions", "-fno-rtti",
                 "-pthread", "-shared", "-o", tmp,
                 os.path.join(SRC_DIR, "bls12381.cc")],
                check=True, capture_output=True, timeout=600)
            os.replace(tmp, so)
        cdll = ctypes.CDLL(so)
        u8p = ctypes.c_char_p
        for name, args in (
                ("ntv_g1_base_mul", [u8p, u8p]),
                ("ntv_g2_base_mul", [u8p, u8p]),
                ("ntv_hash_to_g1_aff", [u8p, ctypes.c_int, u8p,
                                        ctypes.c_int, u8p]),
                ("ntv_hash_to_g2_aff", [u8p, ctypes.c_int, u8p,
                                        ctypes.c_int, u8p]),
                ("ntv_sign_g1", [u8p, u8p, ctypes.c_int, u8p, ctypes.c_int,
                                 u8p]),
                ("ntv_sign_g2", [u8p, u8p, ctypes.c_int, u8p, ctypes.c_int,
                                 u8p]),
                ("ntv_verify_g1sig", [u8p, u8p, ctypes.c_int, u8p,
                                      ctypes.c_int, u8p]),
                ("ntv_verify_g2sig", [u8p, u8p, ctypes.c_int, u8p,
                                      ctypes.c_int, u8p])):
            fn = getattr(cdll, name)
            fn.argtypes = args
            fn.restype = ctypes.c_int
        _LIB = cdll
        return _LIB


def base_mul(group: str, sk: bytes) -> bytes:
    """sk times the group's generator, compressed."""
    out = ctypes.create_string_buffer(48 if group == "G1" else 96)
    fn = lib().ntv_g1_base_mul if group == "G1" else lib().ntv_g2_base_mul
    fn(sk, out)
    return out.raw


def hash_to_curve(group: str, msg: bytes, dst: bytes) -> bytes:
    """RFC 9380 hash_to_curve, affine coordinates (G2: x.c0 x.c1 y.c0
    y.c1), 48 bytes each, big-endian."""
    out = ctypes.create_string_buffer(96 if group == "G1" else 192)
    fn = lib().ntv_hash_to_g1_aff if group == "G1" \
        else lib().ntv_hash_to_g2_aff
    if fn(msg, len(msg), dst, len(dst), out) != 0:
        raise RuntimeError("reference hash_to_curve failed")
    return out.raw


def beacon_message(chained: bool, round_: int, prev_sig) -> bytes:
    h = hashlib.sha256()
    if chained and prev_sig:
        h.update(bytes(prev_sig))
    h.update(int(round_).to_bytes(8, "big"))
    return h.digest()


def verify(sig_group: str, public_key: bytes, msg: bytes, dst: bytes,
           sig: bytes) -> bool:
    """Exact BLS verification of one signature, subgroup checks included."""
    sig = bytes(sig)
    if len(sig) != (48 if sig_group == "G1" else 96):
        return False
    fn = lib().ntv_verify_g1sig if sig_group == "G1" \
        else lib().ntv_verify_g2sig
    return fn(public_key, msg, len(msg), dst, len(dst), sig) == 1


class Chain:
    """One chain identity: its scheme's groups, DST and chaining rule, and
    a 1-of-1 signing key drawn from the seed."""

    def __init__(self, sig_group: str, chained: bool, dst: bytes, seed: int):
        if sig_group not in ("G1", "G2"):
            raise ValueError(f"signature group {sig_group!r}")
        self.sig_group = sig_group
        self.chained = chained
        self.dst = dst
        self.sig_len = 48 if sig_group == "G1" else 96
        h = hashlib.sha512(b"bench-key" + str(seed).encode()).digest()
        self._sk = (int.from_bytes(h, "big") % (R_ORDER - 1) + 1) \
            .to_bytes(32, "big")
        # the public key lies in the other group
        self.public_key = base_mul("G2" if sig_group == "G1" else "G1",
                                   self._sk)

    def message(self, round_: int, prev_sig) -> bytes:
        return beacon_message(self.chained, round_, prev_sig)

    def sign(self, round_: int, prev_sig=None) -> bytes:
        msg = self.message(round_, prev_sig)
        out = ctypes.create_string_buffer(self.sig_len)
        fn = lib().ntv_sign_g1 if self.sig_group == "G1" \
            else lib().ntv_sign_g2
        if fn(self._sk, msg, len(msg), self.dst, len(self.dst), out) != 0:
            raise RuntimeError(f"reference signing of round {round_} failed")
        return out.raw

    def verify(self, round_: int, prev_sig, sig: bytes) -> bool:
        """Exact BLS verification of one beacon, subgroup checks included."""
        return verify(self.sig_group, self.public_key,
                      self.message(round_, prev_sig), self.dst, sig)

    def verify_many(self, items, threads: int = 0):
        """[(round, prev_sig, sig)] -> [bool], on host threads (the
        library drops the interpreter lock inside each call)."""
        items = list(items)
        n = threads or max(1, min(16, (os.cpu_count() or 2) - 1))
        with ThreadPoolExecutor(max_workers=n) as ex:
            return list(ex.map(lambda it: self.verify(*it), items,
                               chunksize=16))
