"""One run of one cell: set-up, the measured window, the check.

`run_cell` does everything below the chip gate, so a CPU rehearsal can
call it at a tiny size.  What a traffic mix does is the loop it names
(`harness/loops/<loop>.py`); this file holds what every loop shares: the
daemon's verify service, the window, the trace, the gate that the timed
path stayed on its device, and the reference's anchors.  It returns the
result object that `run.py` prints as its last line.
"""

import os
import shutil
import sys
import tempfile
import time

from . import check
from . import trace as tracemod
from .window import VerifyProxy, Window

# A traced run traces the first TRACE_SECONDS of its window and closes the
# window there: its metrics are shares and per-round rates, and exporting
# a whole 20 s window of G1 verification (3.3 M device op events) took
# about 75 s on a TPU v5 lite host, past the time a run may take.
TRACE_SECONDS = 2.0

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class _CompileCounter:
    """Counts lowerings and backend compiles while armed (the window),
    and sums the seconds of every JAX compile-path event before that
    (set-up: tracing, lowering, compiling or loading from the cache)."""

    def __init__(self):
        self.armed = False
        self.n = 0
        self.setup = {}         # event -> [count, seconds]

    def __call__(self, event, secs, **_kw):
        if self.armed:
            if event in COMPILE_EVENTS:
                self.n += 1
        elif event.startswith("/jax/"):
            c = self.setup.setdefault(event.rsplit("/", 1)[-1], [0, 0.0])
            c[0] += 1
            c[1] += secs

    def summary(self) -> str:
        return ", ".join(f"{k} {n}x {s:.1f} s" for k, (n, s) in sorted(
            self.setup.items(), key=lambda kv: -kv[1][1]) if s >= 0.5)


class Env:
    """What a loop's `Run` is given: the cell's configuration and mix,
    the seed, a scratch directory, the daemon's `Config` and verify
    service, and the window."""

    def __init__(self, config, traffic, seed, tmp, daemon, service, window,
                 device, verify_wrap, backend):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.tmp, self.daemon, self.service = tmp, daemon, service
        self.window = window
        self._device, self._wrap, self._backend = device, verify_wrap, backend

    @property
    def platform(self) -> str:
        """Where the handles' work has to run: the chip, or in CPU
        rehearsals the host backend or an injected one."""
        if self._backend is not None:
            return "custom"
        return "tpu" if self._device else "host"

    def verifier(self, scheme, public_key: bytes, fixture=None):
        """A timed verifier over a handle of the verify service, built as
        the daemon builds it."""
        kw = {}
        if self._backend is not None:
            from drand_tpu.crypto.hostverify import HostBatchVerifier
            kw = {"backend": self._backend,
                  "fallback": HostBatchVerifier(scheme, public_key)}
        handle = self.service.handle(scheme, public_key, device=self._device,
                                     **kw)
        verify = handle.verify_batch if self._wrap is None \
            else self._wrap(handle.verify_batch, fixture)
        return VerifyProxy(verify, self.window, handle.kind)


def fell_back(stats: dict, platform: str) -> int:
    """Failovers and watchdog trips since the service started, plus
    handles whose work is not on `platform`: each means verdicts that the
    timed path may not have produced."""
    off = sum(1 for p in stats["platforms"].values() if p != platform)
    return stats["failovers"] + stats["watchdog_trips"] + off


def run_cell(spec, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, device: bool = True, verify_wrap=None,
             backend=None) -> dict:
    """Run `workload` once.  `device=False` gives the verify service a
    host backend (CPU rehearsals); `verify_wrap(verify_batch, fixture)`
    replaces the handle's verify call (faults and controls in tests);
    `backend` is a verifier put under the handle in the device
    backend's place, with the host one to fail over to (tests)."""
    import jax
    from drand_tpu.core.config import Config
    from drand_tpu.crypto import batch

    cell = spec.workload(workload)
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    loop = spec.loop(traffic["loop"])
    tmp = tempfile.mkdtemp(prefix="bench-")
    trace_dir = os.path.join(tmp, "trace")
    counter = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        daemon = Config(folder=os.path.join(tmp, "daemon"),
                        **config.get("config_overrides", {}))
        annotate = jax.profiler.TraceAnnotation if trace else None
        window = Window(min(seconds, TRACE_SECONDS) if trace else seconds,
                        annotate)
        svc = None
        run = None
        try:
            svc = daemon.verify_service()
            env = Env(config, traffic, seed, tmp, daemon, svc, window,
                      device, verify_wrap, backend)
            run = loop.Run(env)
            log(f"setup: {run.setup()}")
            log(f"setup: jax events {counter.summary()}")
            s0 = svc.stats()
            off_warm = fell_back(s0, env.platform)
            d0 = batch.dispatch_count()
            if trace:
                jax.profiler.start_trace(trace_dir)
            setup_s = time.monotonic() - t_start
            counter.armed = True
            what = run.measure()
            counter.armed = False
            if trace:
                jax.profiler.stop_trace()
            s1 = svc.stats()
            d1 = batch.dispatch_count()
            dev0 = jax.devices()[0]
            mstats = dev0.memory_stats() or {}
            device_info = {"platform": dev0.platform,
                           "kind": dev0.device_kind,
                           "count": len(jax.devices()),
                           "memory_peak_bytes":
                               int(mstats.get("peak_bytes_in_use", 0))}
        finally:
            if svc is not None:
                svc.stop()
            if run is not None:
                run.close()
        off = off_warm + fell_back(s1, env.platform)
        log(f"window: {window.rounds} rounds in {window.elapsed:.3f} s over "
            f"{what}, {len(window.chunks)} chunks; service dispatches "
            f"{s1['dispatches'] - s0['dispatches']}; failovers "
            f"{s1['failovers']} and watchdog trips {s1['watchdog_trips']} "
            f"since start, handles on {sorted(set(s1['platforms'].values()))}"
            f"; lowerings+compiles in window {counter.n}")
        red = None
        if trace:
            t_red = time.monotonic()
            red = tracemod.reduce(tracemod.load(trace_dir))
            log(f"trace: reduced in {time.monotonic() - t_red:.1f} s")
            device_info["busy_s"] = red["busy_s"]
            device_info["window_s"] = red["window_s"]
        rec = {"rounds": window.rounds, "window_s": window.elapsed,
               "setup_s": setup_s, "spans": window.spans,
               "stats0": s0, "stats1": s1, "dispatches": d1 - d0,
               "trace": red, "compiles_in_window": counter.n}
        t_check = time.monotonic()
        checks, compared = run.check()
        checks["anchor_mismatch"] = (check.anchor_mismatch(), 0)
        checks["fell_back"] = (off, 0)
        log(f"check: {compared} rounds against the reference in "
            f"{time.monotonic() - t_check:.1f} s")
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
        shutil.rmtree(tmp, ignore_errors=True)

    metrics = {}
    for m in spec.metrics(workload, trace):
        v = spec.reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    # a run that left the timed path has no round it can vouch for
    failed = checks["unanswered"][0] + (window.rounds if off else 0)
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": window.submitted,
              "failed": failed,
              "metrics": metrics, "device": device_info}
    if red is not None:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result
