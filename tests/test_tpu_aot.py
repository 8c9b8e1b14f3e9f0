"""Ahead-of-time compiles of the main Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler that the installed libtpu carries
compiles for a topology it is only told about, and refuses what the chip
would refuse (unaligned slices, too much fast memory, a kernel Mosaic
cannot lower).  The kernels are compiled at the production width of the
verify path (8,192 lanes; the G1 GLV ladder runs on 2 x 8,192).  The
topology is described inside a fixture, never at import: only one process
may load libtpu, and every xdist worker imports this file.  Miller,
final exponentiation and the G2 kernels take minutes each and are left to
the compile rehearsal recorded in CHANGES.md.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from drand_tpu.crypto.host.params import P, X
from drand_tpu.ops import pallas_field as PF

LANES = 8192
U32 = jnp.uint32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"      # libtpu logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    if old_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = old_log


def _spec(one_chip, shape, dtype=U32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiles_with_kernel(one_chip, call, *shapes):
    args = [_spec(one_chip, *s) if isinstance(s[0], tuple)
            else _spec(one_chip, s) for s in shapes]
    text = jax.jit(call).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _consts():
    return [((PF.NL, PF.TILE),)] * 2


def test_pow_chain_compiles_for_v5e(one_chip):
    e = (P - 3) // 4
    _compiles_with_kernel(
        one_chip, PF._pow_call(e, LANES),
        ((e.bit_length(),), jnp.int32), *_consts(), (PF.NL, LANES))


def test_fixed_ladder_g1_compiles_for_v5e(one_chip):
    k = -X
    _compiles_with_kernel(
        one_chip, PF._ladder_fixed_call("G1", k, LANES),
        ((k.bit_length(),), jnp.int32), *_consts(),
        *[(PF.NL, LANES)] * 3)


def test_glv_ladder_g1_compiles_for_v5e(one_chip):
    lanes = 2 * LANES
    _compiles_with_kernel(
        one_chip, PF._ladder_glv_mixed_call("G1", 64, lanes),
        *_consts(), *[(PF.NL, lanes)] * 6, *[(64, lanes)] * 2)


def test_point_sum_g1_compiles_for_v5e(one_chip):
    _compiles_with_kernel(
        one_chip, PF._sum_call("G1", LANES),
        *_consts(), *[(PF.NL, LANES)] * 3)
