"""The plain reference for threshold BLS: a dealer's Shamir shares, each
node's partial signature and the group's beacon, on the reference library
(`refbls`).  Nothing here imports the program.

The dealer's polynomial has degree t - 1 and coefficients drawn from the
seed; node i's share is its value at x = i + 1, by Horner mod r (drand's
share indexing, kyber/share).  A partial is be16(i) || the share's BLS
signature of the message (kyber/sign/tbls wire form).  Each share's public
key, and each commitment, is the coefficient or share times the key
group's generator.  The beacon the group must produce is the signature
under the constant term: Lagrange recovery of any t valid partials gives
exactly that, so no Lagrange step is needed to know the right answer.
"""

import ctypes
import hashlib

from . import refbls

# The order r of G1, G2 and the scalars (the BLS12-381 parameters; drand's
# kyber/pairing/bls12381).  `refbls.R_ORDER` differs from it in one digit,
# which is harmless there (it only reduces a key drawn from the seed) but
# not for share arithmetic; `Dealer` checks this value against the library
# (r times either generator is the point at infinity).
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
_INFINITY = {"G1": b"\xc0" + bytes(47), "G2": b"\xc0" + bytes(95)}


def _sign(sig_group: str, sk: int, msg: bytes, dst: bytes) -> bytes:
    out = ctypes.create_string_buffer(48 if sig_group == "G1" else 96)
    lib = refbls.lib()
    fn = lib.ntv_sign_g1 if sig_group == "G1" else lib.ntv_sign_g2
    if fn(sk.to_bytes(32, "big"), msg, len(msg), dst, len(dst), out) != 0:
        raise RuntimeError("reference signing failed")
    return out.raw


class Dealer:
    """A t-of-n group under `scheme`, its polynomial drawn from `seed`."""

    def __init__(self, scheme: str, n: int, t: int, seed: int):
        if not 1 <= t <= n < 1 << 15:
            raise ValueError(f"threshold {t} of {n}")
        self.sig_group, self.chained, self.dst = refbls.SCHEMES[scheme]
        self.key_group = "G2" if self.sig_group == "G1" else "G1"
        self.n, self.t = n, t
        r = R
        if self._pub(r) != _INFINITY[self.key_group]:
            raise RuntimeError("r is not the order of the reference's group")
        self.coeffs = []
        for j in range(t):
            h = hashlib.sha512(b"bench-dealer" + str(seed).encode()
                               + j.to_bytes(2, "big")).digest()
            self.coeffs.append(int.from_bytes(h, "big") % (r - 1) + 1)
        self.shares = []
        for i in range(n):
            acc = 0
            for c in reversed(self.coeffs):
                acc = (acc * (i + 1) + c) % r
            self.shares.append(acc)
        self.commits = [self._pub(c) for c in self.coeffs]
        self.public_key = self.commits[0]
        self.share_keys = [self._pub(s) for s in self.shares]

    def _pub(self, sk: int) -> bytes:
        return refbls.base_mul(self.key_group, sk.to_bytes(32, "big"))

    def message(self, round_: int, prev_sig) -> bytes:
        return refbls.beacon_message(self.chained, round_, prev_sig)

    def beacon(self, msg: bytes) -> bytes:
        """The group's signature of `msg`: under the constant term."""
        return _sign(self.sig_group, self.coeffs[0], msg, self.dst)

    def partial(self, i: int, msg: bytes) -> bytes:
        return i.to_bytes(2, "big") + _sign(self.sig_group, self.shares[i],
                                            msg, self.dst)

    def verify_partial(self, msg: bytes, partial: bytes) -> bool:
        """Exact check of one wire partial against its signer's share key,
        subgroup checks included."""
        partial = bytes(partial)
        i = int.from_bytes(partial[:2], "big")
        if len(partial) < 2 or i >= self.n:
            return False
        return refbls.verify(self.sig_group, self.share_keys[i], msg,
                             self.dst, partial[2:])

    def verify_beacon(self, msg: bytes, sig: bytes) -> bool:
        return refbls.verify(self.sig_group, self.public_key, msg, self.dst,
                             sig)
