"""The compile-cache rule every entry point shares (drand_tpu/compile_cache.py)
and chip_smoke.py's refusal to run without a TPU."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

from drand_tpu import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: calls.append((name, val)))
    return calls


_NO_LOCATIONS = ("jax_traceback_in_locations_limit", 0)


def test_env_dir_is_used_and_no_other_dir_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    calls = _record_updates(monkeypatch)
    assert compile_cache.enable() == str(tmp_path)
    assert calls == [_NO_LOCATIONS]     # JAX reads the env var itself


def test_default_dir_is_the_checkout_jax_cache(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    calls = _record_updates(monkeypatch)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable() == want
    assert calls == [_NO_LOCATIONS, ("jax_compilation_cache_dir", want)]


# Lowers program B, after program A (which shares its Pallas kernel) or
# alone, for a described v5e, and prints B's kernel bodies: the bytes the
# persistent cache key is made of.
_ORDER = r"""
import os, re, sys
sys.path.insert(0, sys.argv[1])
os.environ["TPU_LOG_DIR"] = "disabled"
import jax, jax.numpy as jnp
from drand_tpu import compile_cache
compile_cache.enable()
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from drand_tpu.crypto.host.params import P
from drand_tpu.ops import pallas_field as PF
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("SKIP", e)
    sys.exit(0)
sh = SingleDeviceSharding(topo.devices[0])
spec = lambda shape, dt=jnp.uint32: jax.ShapeDtypeStruct(shape, dt,
                                                         sharding=sh)
e = (P - 3) // 4
args = (spec((e.bit_length(),), jnp.int32), spec((PF.NL, PF.TILE)),
        spec((PF.NL, PF.TILE)), spec((PF.NL, 2048)))
progs = {"A": jax.jit(lambda *a: PF._pow_call(e, 2048)(*a)),
         "B": jax.jit(lambda *a: PF._pow_call(e, 2048)(*a) + 1)}
for name in sys.argv[2]:
    text = progs[name].lower(*args).as_text()
print(re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)', text))
"""


def test_kernel_body_does_not_depend_on_the_trace_order(tmp_path):
    """The same program lowers to the same bytes whether or not another
    program traced its kernel first: the persistent cache can hit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", ALLOW_MULTIPLE_LIBTPU_LOAD="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = [subprocess.run([sys.executable, "-c", _ORDER, REPO, order],
                          env=env, capture_output=True, text=True,
                          timeout=300) for order in ("AB", "B")]
    for r in out:
        assert r.returncode == 0, r.stderr[-2000:]
    last = [r.stdout.strip().splitlines()[-1] for r in out]
    if last[0].startswith("SKIP"):
        pytest.skip(last[0])
    assert last[0] == last[1] != "[]"


def test_default_dir_does_not_depend_on_pid_time_or_tmp():
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV}
    env["TMPDIR"] = "/nonexistent-tmp"
    code = ("from drand_tpu import compile_cache; "
            "print(compile_cache.DEFAULT_DIR)")
    seen = {subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           capture_output=True, text=True,
                           timeout=60).stdout.strip()
            for _ in range(2)}
    assert seen == {os.path.join(REPO, ".jax_cache")}


def _smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_chip_smoke_refuses_cpu_without_traceback():
    r = _smoke(REPO)
    assert r.returncode != 0
    assert "no TPU" in r.stdout.splitlines()[-1]
    assert '"ok": true' not in r.stdout
    assert "Traceback" not in r.stdout + r.stderr


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _smoke(tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
