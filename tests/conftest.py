"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Tests must be deterministic and runnable without TPU hardware.  The 8 virtual
CPU devices back the sharding tests in test_multichip.py and
__graft_entry__.dryrun_multichip; the chip itself is driven by chip_smoke.py
(through the chip tool), and tests/test_tpu_aot.py compiles the main
kernels for a described v5e without one.
"""

import os

# Must run before the first `import jax` anywhere in the test session.
# XLA_FLAGS must be BYTE-IDENTICAL from run to run (no leading space):
# the raw env string lands in the persistent-cache key, so a cosmetic
# difference forces a from-scratch compile of the big sharded programs
# inside the suite (r5 finding; r4 postmortem).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if f and not f.startswith("--xla_force_host_platform_device_count")]
_flags.append("--xla_force_host_platform_device_count=8")
os.environ["XLA_FLAGS"] = " ".join(_flags)
import jax  # noqa: E402

# The persistent cache is ON for the CPU suite, at the place every entry
# point uses (drand_tpu/compile_cache.py: $JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache/).  DRAND_TPU_TEST_CACHE=0 turns it off.
if os.environ.get("DRAND_TPU_TEST_CACHE", "1") != "0":
    from drand_tpu import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 5)

    # Both patches below reach into jax._src private modules (no public
    # hook exists for either failure mode — docs/jax-cache-issues.md holds
    # the upstream issue text); they are written against the installed
    # jax 0.9.0.
    #
    # jax's filesystem cache writes are a bare write_bytes with NO lock
    # when eviction is disabled (jax/_src/lru_cache.py) — two xdist
    # workers cold-compiling the same program race the same file and the
    # interleaved result is a plausible-looking entry that SEGFAULTS the
    # deserializer on every later read (the round-4 "poisoned cache"
    # postmortem).  Make writes atomic: unique temp file + os.replace,
    # last full write wins.
    import time as _time
    import uuid

    from jax._src import compilation_cache as _cc
    from jax._src import compiler as _jcompiler
    from jax._src import lru_cache as _jlc

    def _atomic_put(self, key, val):
        if not key:
            raise ValueError("key cannot be empty")
        cache_path = self.path / f"{key}{_jlc._CACHE_SUFFIX}"
        if cache_path.exists():
            return
        tmp = self.path / f".tmp-{uuid.uuid4().hex}"
        tmp.write_bytes(val)
        os.replace(str(tmp), str(cache_path))

    _jlc.LRUCache.put = _atomic_put

    # Second failure mode (the "round-2 serialize segfault"): XLA:CPU
    # executable SERIALIZATION segfaults on certain big programs — after a
    # successful compile, during the cache write.  Run the whole
    # serialize+write in a forked child: a crash there costs only the
    # cache entry, never the test process.  The atomic temp+rename above
    # makes a killed child harmless.
    _orig_put_exec = _cc.put_executable_and_time

    def _forked_put_executable(cache_key, module_name, executable, backend,
                               compile_time):
        pid = os.fork()
        if pid == 0:
            code = 0
            try:
                _orig_put_exec(cache_key, module_name, executable, backend,
                               compile_time)
            except BaseException:
                code = 1
            finally:
                os._exit(code)
        deadline = _time.time() + 300
        while _time.time() < deadline:
            done, _status = os.waitpid(pid, os.WNOHANG)
            if done:
                return
            _time.sleep(0.05)
        os.kill(pid, 9)                      # fork-deadlocked child
        os.waitpid(pid, 0)

    _cc.put_executable_and_time = _forked_put_executable
    _jcompiler.compilation_cache.put_executable_and_time = \
        _forked_put_executable
else:
    jax.config.update("jax_enable_compilation_cache", False)


# Device-kernel files cold-compile for many minutes per program.  Run
# them LAST so a time-bounded run still exercises the whole framework
# first — and mark them out of the tier-1 budget entirely (below).
# Matched by exact file stem / exact test name (NOT nodeid substring:
# now that a match deselects from tier-1 rather than just reordering, a
# future tests/test_batching.py must not silently vanish from the gate).
_HEAVY_FILES = {"test_batch", "test_batch_sign", "test_multichip",
                "test_ops_curve_pairing", "test_partials",
                "test_aggregate_reference",
                "test_ops_pallas", "test_ops_pallas_pairing"}
# the one integrity test that runs the DEVICE verifier: ordered into the
# heavy bucket (after test_batch, which compiles the same pad-8 RLC
# pipeline) so a cold XLA cache can't stall the fast group
_HEAVY_TESTS = {"test_chain_doctor_scan_clean_uses_device_verifier"}


def _is_heavy(item) -> bool:
    return item.path.stem in _HEAVY_FILES \
        or item.name.split("[")[0] in _HEAVY_TESTS


def pytest_collection_modifyitems(config, items):
    """Order the heavy compile-bound bucket last AND gate it structurally
    (ROADMAP "known friction", ISSUE 6 satellite): on the 2-core no-TPU
    container a cold XLA cache costs tens of minutes for the big pairing
    programs, which blew the tier-1 870 s budget (rc=124) on every run
    where the persistent cache above was cold or invalidated (any edit
    that shifts lines in a traced file rewrites the Mosaic cache keys).
    The heavy bucket is therefore auto-marked `slow` + `heavy_compile`:
    tier-1 (`-m 'not slow'`) stays green and budget-bound, while the
    device pipelines keep their coverage via

      * naming a file directly (`pytest tests/test_batch.py` — no -m
        filter, everything runs; the "pass standalone" workflow),
      * `pytest -m heavy_compile tests/` (just the device bucket), or
      * DRAND_TPU_RUN_HEAVY=1 (suppresses the auto-`slow` mark so a
        nightly/driver run with a warm cache exercises everything).
    """
    # `committee`-marked tests (n~1000 Handel/DKG, ISSUE 13) ride the
    # same gating: ordered last, auto-`slow` unless DRAND_TPU_RUN_HEAVY=1
    # (or the file is named directly — no -m filter applies then)
    def _gated(item):
        return _is_heavy(item) or \
            item.get_closest_marker("committee") is not None

    items.sort(key=_gated)
    run_heavy = os.environ.get("DRAND_TPU_RUN_HEAVY", "0") == "1"
    for it in items:
        if _is_heavy(it):
            it.add_marker(pytest.mark.heavy_compile)
        if _gated(it) and not run_heavy:
            it.add_marker(pytest.mark.slow)


# XLA's CPU compiler recurses deeply on the big scan/pairing programs.
# Under xdist the test body runs on an execnet-spawned thread whose stack
# is FIXED at creation (unlike the main thread's demand-grown stack), and
# the deepest programs (e.g. the G2 sign pipeline: a 758-step Fp2 pow
# scan nested under a 256-step ladder scan) segfault mid-compile there —
# reproducibly under `-n 4`, never under `-n 0`.  Fix at the harness
# level: run every test body in a fresh thread with a large explicit
# stack when inside an xdist worker.
_BIG_STACK = 512 * 1024 * 1024  # virtual reservation; touched pages only

import pytest  # noqa: E402
import threading  # noqa: E402


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    if os.environ.get("PYTEST_XDIST_WORKER") is None:
        return None                      # main process: growable stack
    import inspect
    if inspect.iscoroutinefunction(getattr(pyfuncitem, "obj", None)):
        return None                      # let an async plugin drive it
    result = {}

    def run():
        try:
            fn = pyfuncitem.obj
            kwargs = {name: pyfuncitem.funcargs[name]
                      for name in pyfuncitem._fixtureinfo.argnames}
            result["value"] = fn(**kwargs)
        except BaseException as e:       # re-raised in the worker thread
            result["exc"] = e

    old = threading.stack_size(_BIG_STACK)
    try:
        th = threading.Thread(target=run, name="bigstack-test")
        th.start()
        th.join()
    finally:
        threading.stack_size(old)
    if "exc" in result:
        raise result["exc"]
    return True
