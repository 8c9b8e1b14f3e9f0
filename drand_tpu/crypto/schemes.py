"""Scheme registry: the four drand beacon schemes as declarative configs.

Mirrors the capability surface of the reference's crypto.Scheme
(crypto/schemes.go:46-204):

  pedersen-bls-chained    keys G1 (48B), sigs G2 (96B), digest = H(prevSig||round)
  pedersen-bls-unchained  keys G1 (48B), sigs G2 (96B), digest = H(round)
  bls-unchained-on-g1     keys G2 (96B), sigs G1 (48B), digest = H(round)
  bls-unchained-g1-rfc9380  the same with the RFC 9380 G1 DST (quicknet)

DST note: this era's kyber-bls12381 uses the G2-suite DST string for *both*
sig groups (the historical short-sig quirk) — pinned here by the mainnet
known-answer vectors (crypto/schemes_test.go:90-115), which only verify with
DST "BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_NUL_" on the G1 scheme too.
bls-unchained-g1-rfc9380 is the scheme that fixed it (DST_G1).

Host (pure-Python) sign/verify lives here; the batched device path is in
drand_tpu.crypto.jax (batch_verify / tbls kernels).
"""

import hashlib
import os
import secrets
from dataclasses import dataclass
from typing import Optional

from .host.params import R, DST_G1, DST_G2
from .host import curve as C
from .host import h2c as H2C
from .host import serialize as S
from .host.pairing import pairing_check

DEFAULT_SCHEME_ID = "pedersen-bls-chained"
UNCHAINED_SCHEME_ID = "pedersen-bls-unchained"
SHORT_SIG_SCHEME_ID = "bls-unchained-on-g1"
RFC9380_SCHEME_ID = "bls-unchained-g1-rfc9380"


def _h2c_g1(msg, dst):
    from .host import native
    if native.available():
        return native.hash_to_g1(msg, dst)
    return H2C.hash_to_curve_g1(msg, dst)


def _h2c_g2(msg, dst):
    from .host import native
    if native.available():
        return native.hash_to_g2(msg, dst)
    return H2C.hash_to_curve_g2(msg, dst)


class GroupG1:
    """kyber.Group-equivalent handle for G1."""
    name = "bls12-381.G1"
    point_len = 48
    curve = C.G1
    to_bytes = staticmethod(S.g1_to_bytes)
    from_bytes = staticmethod(S.g1_from_bytes)
    hash_to_curve = staticmethod(_h2c_g1)


class GroupG2:
    name = "bls12-381.G2"
    point_len = 96
    curve = C.G2
    to_bytes = staticmethod(S.g2_to_bytes)
    from_bytes = staticmethod(S.g2_from_bytes)
    hash_to_curve = staticmethod(_h2c_g2)


@dataclass(frozen=True)
class Scheme:
    """A named bundle of groups + digest rules (schemes.go:46-67 analogue)."""
    id: str
    sig_group: object     # group signatures live on
    key_group: object     # group public keys live on
    chained: bool
    dst: bytes = DST_G2

    # -- digest (schemes.go:106-114 / 147-151) -----------------------------
    def digest_beacon(self, round_: int, prev_sig: Optional[bytes]) -> bytes:
        h = hashlib.sha256()
        if self.chained:
            if prev_sig:
                h.update(prev_sig)
            h.update(round_.to_bytes(8, "big"))
        else:
            h.update(round_.to_bytes(8, "big"))
        return h.digest()

    # -- host sign/verify (native C fast path, pure-Python fallback) --------
    def sign(self, secret: int, msg: bytes) -> bytes:
        from .host import native
        if native.available():
            return (native.sign_g2 if self.sig_group is GroupG2
                    else native.sign_g1)(secret, msg, self.dst)
        hp = self.sig_group.hash_to_curve(msg, self.dst)
        return self.sig_group.to_bytes(self.sig_group.curve.mul(hp, secret))

    def verify(self, pub_point, msg: bytes, sig: bytes) -> bool:
        """Verify one signature on the host (latency path)."""
        if pub_point is None:
            return False
        from .host import native
        if native.available():
            if self.sig_group is GroupG2:
                return native.verify_g2sig(pub_point, msg, self.dst, sig)
            return native.verify_g1sig(pub_point, msg, self.dst, sig)
        try:
            sp = self.sig_group.from_bytes(sig)
        except (ValueError, AssertionError):
            return False
        if sp is None:
            return False
        hp = self.sig_group.hash_to_curve(msg, self.dst)
        if self.sig_group is GroupG2:
            # pk on G1: e(pk, H(m)) == e(g1, sig)
            return pairing_check([(pub_point, hp), (C.G1.neg(C.G1.gen), sp)])
        # pk on G2: e(H(m), pk) == e(sig, g2)
        return pairing_check([(hp, pub_point), (C.G1.neg(sp), C.G2.gen)])

    def verify_beacon(self, pub_bytes_or_point, round_: int, prev_sig, sig: bytes) -> bool:
        pub = pub_bytes_or_point
        if isinstance(pub, (bytes, bytearray)):
            try:
                pub = self.key_group.from_bytes(bytes(pub))
            except (ValueError, AssertionError):
                return False  # total predicate, like verify() on bad sig bytes
        return self.verify(pub, self.digest_beacon(round_, prev_sig), sig)

    # -- keys ---------------------------------------------------------------
    def keypair(self, seed: Optional[bytes] = None):
        """(secret scalar, public point).  Public key lives on key_group."""
        if seed is None:
            s = secrets.randbelow(R - 1) + 1
        else:
            s = int.from_bytes(hashlib.sha512(seed).digest(), "big") % (R - 1) + 1
        return s, self.key_group.curve.mul(self.key_group.curve.gen, s)

    def public_bytes(self, pub_point) -> bytes:
        return self.key_group.to_bytes(pub_point)


def randomness_from_signature(sig: bytes) -> bytes:
    """randomness = SHA256(signature)  (schemes.go:249-252)."""
    return hashlib.sha256(sig).digest()


_SCHEMES = {
    DEFAULT_SCHEME_ID: Scheme(DEFAULT_SCHEME_ID, GroupG2, GroupG1, chained=True),
    UNCHAINED_SCHEME_ID: Scheme(UNCHAINED_SCHEME_ID, GroupG2, GroupG1, chained=False),
    SHORT_SIG_SCHEME_ID: Scheme(SHORT_SIG_SCHEME_ID, GroupG1, GroupG2, chained=False),
    RFC9380_SCHEME_ID: Scheme(RFC9380_SCHEME_ID, GroupG1, GroupG2,
                              chained=False, dst=DST_G1),
}


def scheme_from_name(name: str) -> Scheme:
    """SchemeFromName (schemes.go:206)."""
    try:
        return _SCHEMES[name]
    except KeyError:
        raise ValueError(f"invalid scheme name {name!r}") from None


def list_schemes():
    return list(_SCHEMES)


def get_scheme_by_id_with_default(id_: str = "") -> Scheme:
    return scheme_from_name(id_ or DEFAULT_SCHEME_ID)


def get_scheme_from_env() -> Scheme:
    """SCHEME_ID env override (schemes.go:239)."""
    return get_scheme_by_id_with_default(os.environ.get("SCHEME_ID", ""))
