"""Metrics + observability (reference: metrics/metrics.go, 535 LoC).

Prometheus series matching the reference's names so existing dashboards
work unchanged: `beacon_discrepancy_latency` (ms between the expected round
time and storage, metrics.go:83-88 / chain/beacon/store.go:156-163),
`last_beacon_round`, `group_size`, `group_threshold`, `dkg_state` /
`reshare_state` (+ timestamps), `drand_node_db`, `error_sending_partial`.

The metrics HTTP server also exposes pprof-equivalent profiling and the
cross-node federation route `/peer/<addr>/metrics` that proxies a group
member's metrics through the gRPC connection we already hold
(metrics.go:408-492) — operators scrape the whole group via one node.

`ThresholdMonitor` (metrics/threshold_monitor.go:12-105): counts distinct
peers with failed partial sends in a sliding one-minute window and
escalates log severity when failures cross threshold/2 and threshold.
"""

import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from prometheus_client import (CollectorRegistry, Counter, Gauge, Histogram,
                               generate_latest)

from .log import Logger

# Four registries, per the reference split (metrics.go:45-51).
PRIVATE = CollectorRegistry()
HTTP = CollectorRegistry()
GROUP = CollectorRegistry()
CLIENT = CollectorRegistry()

# -- label cardinality control ----------------------------------------------
# Prometheus allocates one time series per label combination, so every
# label value must come from a bounded set (the metriclabel lint rule).
# Naturally-unbounded values (peer addresses, tenant names, request-path
# leaves) pass through registered_label(), which caps distinct values per
# namespace and folds the tail into a fallback bucket — a scrape sees the
# first `limit` real values and one "other" series, never an explosion.

_label_sets: Dict[str, set] = {}
_label_lock = threading.Lock()


def registered_label(value, known=None, ns: str = "default",
                     limit: int = 64, fallback: str = "other") -> str:
    """Bound a metric label value.

    With `known`, membership decides: values outside the set collapse to
    `fallback`.  Without it, a first-come registry per `ns` admits up to
    `limit` distinct values; later unseen values collapse to `fallback`.
    """
    v = str(value)
    if known is not None:
        return v if v in known else fallback
    with _label_lock:
        seen = _label_sets.setdefault(ns, set())
        if v in seen:
            return v
        if len(seen) < limit:
            seen.add(v)
            return v
    return fallback

beacon_discrepancy_latency = Gauge(
    "beacon_discrepancy_latency",
    "Difference between the expected round time and the storage time (ms)",
    ["beacon_id"], registry=GROUP)
last_beacon_round = Gauge(
    "last_beacon_round", "Last locally stored beacon round",
    ["beacon_id"], registry=GROUP)
group_size = Gauge(
    "group_size", "Number of nodes in the group", ["beacon_id"],
    registry=GROUP)
group_threshold = Gauge(
    "group_threshold", "Threshold of the group", ["beacon_id"],
    registry=GROUP)
dkg_state = Gauge(
    "dkg_state", "DKG state (0 not started .. 4 done)", ["beacon_id"],
    registry=GROUP)
dkg_state_timestamp = Gauge(
    "dkg_state_timestamp", "When the DKG state last changed", ["beacon_id"],
    registry=GROUP)
reshare_state = Gauge(
    "reshare_state", "Reshare state", ["beacon_id"], registry=GROUP)
reshare_state_timestamp = Gauge(
    "reshare_state_timestamp", "When the reshare state last changed",
    ["beacon_id"], registry=GROUP)
drand_node_db = Gauge(
    "drand_node_db", "Storage engine in use", ["db"], registry=PRIVATE)
# restart observability (fleet harness, ISSUE 18): the gauge is this
# process's start stamp; the counter is seeded from the persisted
# restarts.json in the beacon folder so fleet runs assert restart counts
# from a metrics scrape instead of scraping logs
daemon_start_time_seconds = Gauge(
    "daemon_start_time_seconds", "Unix time this daemon process started",
    registry=PRIVATE)
daemon_restarts_total = Counter(
    "daemon_restarts_total",
    "Daemon starts beyond the first against this beacon folder "
    "(persisted across processes in <folder>/restarts.json)",
    registry=PRIVATE)
error_sending_partial = Counter(
    "error_sending_partial", "Failed partial beacon sends",
    ["beacon_id", "address"], registry=GROUP)
api_call_counter = Counter(
    "api_call_counter", "Public API calls", ["api_method"], registry=HTTP)
http_latency = Histogram(
    "http_response_latency_seconds", "REST edge latency", ["route"],
    registry=HTTP)
client_http_heartbeat = Counter(
    "client_http_heartbeat", "HTTP client watch liveness", ["url"],
    registry=CLIENT)
# Resilience layer (net/resilience.py): per-peer circuit breakers and the
# retry/deadline executor.  `resilience_breaker_state` is 0 closed / 1 open /
# 2 half-open; transitions carry the target state as a label so a scrape
# shows a peer getting quarantined and later probed back in.
breaker_state = Gauge(
    "resilience_breaker_state",
    "Per-peer circuit breaker state (0 closed, 1 open, 2 half-open)",
    ["scope", "address"], registry=GROUP)
breaker_transitions = Counter(
    "resilience_breaker_transitions_total",
    "Circuit breaker state transitions", ["scope", "address", "state"],
    registry=GROUP)
retries_total = Counter(
    "resilience_retries_total", "Retry attempts after a failed call",
    ["scope", "op"], registry=GROUP)
deadline_exceeded_total = Counter(
    "resilience_deadline_exceeded_total",
    "Operations abandoned because their overall budget was spent",
    ["scope", "op"], registry=GROUP)
# Chain-integrity subsystem (chain/integrity.py + tools/chain_doctor.py):
# the scan/quarantine/repair counters live next to the breaker metrics so
# one scrape answers both "is the network healthy" and "is the disk
# healthy".  `verifier` is host|device — the acceptance check that a scan
# really ran through the batched device path reads this label.
integrity_beacons_scanned = Counter(
    "chain_integrity_beacons_scanned_total",
    "Beacon rounds examined by integrity scans",
    ["beacon_id", "verifier", "trigger"], registry=GROUP)
integrity_corrupt_found = Counter(
    "chain_integrity_corrupt_found_total",
    "Corrupt/missing rounds flagged by integrity scans",
    ["beacon_id", "kind", "trigger"], registry=GROUP)
integrity_quarantined = Counter(
    "chain_integrity_quarantined_total",
    "Corrupt rounds deleted (quarantined) pending re-fetch",
    ["beacon_id"], registry=GROUP)
integrity_repaired = Counter(
    "chain_integrity_repaired_total",
    "Quarantined/missing rounds re-fetched, re-verified and restored",
    ["beacon_id"], registry=GROUP)
# Resident verify service (crypto/verify_service.py): every verify
# consumer submits through one daemon-owned pipeline; these series answer
# "is coalescing working" (fill ratio up, dispatches well below requests)
# and "are live rounds starved" (live queue depth, preemption count).
verify_requests = Counter(
    "verify_service_requests_total",
    "Verification submissions accepted by the verify service",
    ["lane"], registry=PRIVATE)
verify_dispatches = Counter(
    "verify_service_dispatches_total",
    "Device/host dispatches issued by the verify service "
    "(group = the device group whose stream dispatched)",
    ["lane", "group"], registry=PRIVATE)
verify_queue_depth = Gauge(
    "verify_service_queue_depth",
    "Requests waiting in a verify-service lane", ["lane"],
    registry=PRIVATE)
verify_fill_ratio = Histogram(
    "verify_service_batch_fill_ratio",
    "Real lanes / padded width per coalesced dispatch",
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0),
    registry=PRIVATE)
verify_dispatch_latency = Histogram(
    "verify_service_dispatch_latency_seconds",
    "Verify-service latency split: phase=queue is submit-to-gather wait "
    "(coalescing window + lane contention, per batch), phase=device is "
    "dispatch-to-verdict wall time (per coalesced chunk) — occupancy "
    "regressions show up as device-time growth, overload as queue "
    "growth; host packing is drand_span_seconds_total{span=\"verify.pack\"}",
    ["lane", "phase"], registry=PRIVATE)
verify_inflight = Gauge(
    "verify_service_inflight_depth",
    "Dispatches currently enqueued ahead of the resolve point in the "
    "depth-k pipelined executor (0 when idle)",
    registry=PRIVATE)
verify_preemptions = Counter(
    "verify_service_preemptions_total",
    "Background batches preempted at a chunk boundary by live work",
    registry=PRIVATE)
# Device failure domain (crypto/verify_service.py watchdog/failover):
# `chain` is "<scheme>:<pk hex prefix>" — one series per backend handle.
# backend_state encodes the failover state machine (0 healthy, 1 suspect,
# 2 degraded, 3 probing); failovers count device→host swaps AND host→device
# re-promotions (the `direction` label tells them apart).
verify_failovers = Counter(
    "verify_service_failovers_total",
    "Verify-service backend swaps (device->host and re-promotions)",
    ["chain", "direction"], registry=PRIVATE)
verify_backend_state = Gauge(
    "verify_service_backend_state",
    "Verify backend failover state (0 healthy, 1 suspect, 2 degraded, "
    "3 probing); group = the chain's device-group affinity",
    ["chain", "group"], registry=PRIVATE)
# Multi-device scale-out (crypto/device_pool.py): one series per device
# group — how many devices it owns.  Group membership is static for a
# process; the gauge going to a new label set means the pool was rebuilt.
verify_group_devices = Gauge(
    "verify_service_group_devices",
    "Devices owned by each verify-service device group",
    ["group"], registry=PRIVATE)
verify_watchdog_trips = Counter(
    "verify_service_watchdog_trips_total",
    "Device dispatches abandoned after blowing their watchdog deadline",
    ["chain"], registry=PRIVATE)
verify_probe_latency = Histogram(
    "verify_service_probe_latency_seconds",
    "Canary probe dispatch latency on a degraded device backend",
    ["chain"], registry=PRIVATE)
# Serving-plane admission control (net/admission.py): every inbound
# surface (gRPC listener, REST edge, SyncChain streams) consults one
# controller.  `class` is critical|normal|sheddable, `decision` is
# admitted|shed; `admission_level` is the degradation-ladder rung
# (0 nominal, 1 shed-public, 2 pause-background, 3 shed-normal).
admission_requests = Counter(
    "admission_requests_total",
    "Serving-plane admission decisions",
    ["cls", "decision"], registry=PRIVATE)
admission_wait_seconds = Histogram(
    "admission_wait_seconds",
    "Admission queue wait per admitted request (the ladder's p99 signal)",
    ["cls"],
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.0, 5.0),
    registry=PRIVATE)
admission_level = Gauge(
    "admission_level",
    "Degradation-ladder level (0 nominal .. 3 shed-normal)",
    registry=PRIVATE)
admission_inflight = Gauge(
    "admission_inflight",
    "Requests currently holding an admission token", ["cls"],
    registry=PRIVATE)
admission_background_paused = Gauge(
    "admission_background_paused",
    "1 while the ladder has paused the verify service's background lane",
    registry=PRIVATE)
# Integrity-scan resumability (chain/integrity.py ScanCheckpoint): where
# the latest scheduled scan resumed from (0 = scanned from genesis).
integrity_scan_resumed_from = Gauge(
    "chain_integrity_scan_resumed_from",
    "Round the latest integrity scan resumed from (0 = full rescan)",
    ["beacon_id"], registry=GROUP)
# Two-phase quarantine (chain/store.py tombstones): rows whose corrupt
# anchor was restored and whose own bytes then re-verified — promoted
# back from the quarantine side table instead of re-downloaded.
integrity_promoted = Counter(
    "chain_integrity_promoted_total",
    "Tombstoned rows re-verified against a restored anchor and promoted "
    "back without a peer re-fetch",
    ["beacon_id"], registry=GROUP)
# DKG/reshare lifecycle (core/dkg_journal.py): session outcomes, the
# live session's phase, and whether a reshare output sits staged on disk
# awaiting its transition round.  `result` is success|failed|aborted
# (aborted = a crash-restart found the session mid-flight).
dkg_sessions = Counter(
    "dkg_sessions_total",
    "DKG/reshare sessions by outcome",
    ["beacon_id", "kind", "result"], registry=GROUP)
dkg_phase_gauge = Gauge(
    "dkg_phase",
    "Live DKG session phase (0 idle, 1 setup, 2 deal, 3 response, "
    "4 justification, 5 adopt)",
    ["beacon_id"], registry=GROUP)
reshare_transition_pending = Gauge(
    "reshare_transition_pending",
    "1 while a reshare output is staged on disk awaiting its transition "
    "round (the pending-transition ledger is non-empty)",
    ["beacon_id"], registry=GROUP)

# Committee-scale engine (beacon/handel.py + crypto/dkg_device.py): the
# Handel overlay's session lifecycle, candidate verdicts, send volume and
# demotions — the observable difference between a converging tree and a
# wedged level.
handel_sessions = Counter(
    "handel_sessions_total",
    "Handel per-round sessions by outcome (complete | flushed)",
    ["beacon_id", "result"], registry=GROUP)
handel_candidates = Counter(
    "handel_candidates_total",
    "Incoming candidate aggregates by admission verdict",
    ["beacon_id", "verdict"], registry=GROUP)
handel_sends = Counter(
    "handel_sends_total", "Candidate aggregates sent to level peers",
    ["beacon_id"], registry=GROUP)
handel_demotions = Counter(
    "handel_demotions_total",
    "Peers demoted by the overlay (bad candidates past the limit)",
    ["beacon_id"], registry=GROUP)
handel_active_sessions = Gauge(
    "handel_active_sessions", "Live per-round Handel sessions",
    ["beacon_id"], registry=GROUP)

# Multi-tenant serving (core/tenancy.py, ISSUE 15): per-tenant admission
# decisions, measured device occupancy, and the quota level the
# enforcement planes act on (>= 1 means the tenant is over its
# device-time budget and sheds one degradation-ladder rung early).
tenant_requests = Counter(
    "tenant_requests_total",
    "Admission decisions attributed to a tenant",
    ["tenant", "decision"], registry=PRIVATE)
tenant_device_seconds = Counter(
    "tenant_device_seconds_total",
    "Verify-service device seconds attributed to a tenant (measured off "
    "the pack|queue|device latency split)",
    ["tenant"], registry=PRIVATE)
tenant_quota_level = Gauge(
    "tenant_quota_level",
    "Device-time quota level per tenant (used/allowed over the rolling "
    "window; >= 1 is over quota)",
    ["tenant"], registry=PRIVATE)

# Identity plane (net/identity.py + core/authz.py, ISSUE 19): mTLS cert
# lifecycle on the node-to-node planes and tenant-token verdicts on the
# admission edge.  Every rejected theft attempt lands here with a bounded
# reason label; `identity_rejections` is the series the StolenIdentity
# chaos scenario asserts on.
identity_cert_state = Gauge(
    "identity_cert_state",
    "Local mTLS cert expiry state (0 fresh, 1 grace, 2 expired; grace "
    "and expired both keep serving — rotation is overdue, not fatal)",
    registry=PRIVATE)
identity_cert_reloads = Counter(
    "identity_cert_reloads_total",
    "Cert-dir hot reloads by result (ok | error)",
    ["result"], registry=PRIVATE)
identity_rejections = Counter(
    "identity_rejections_total",
    "Authentication rejections by surface (grpc | rest | handel) and "
    "reason (token REASON_* values, or impersonation)",
    ["surface", "reason"], registry=PRIVATE)
authz_tokens = Counter(
    "authz_tokens_total",
    "Tenant-token lifecycle events (minted | revoked)",
    ["event"], registry=PRIVATE)


# -- program spans and counters ---------------------------------------------
# One in-memory registry of the program's own timing, kept where the work
# happens (the scanner's store reads and its wait for them, the verify
# service's pack, dispatch and verdict, a device program's first call):
# each name holds [count, host seconds].  VerifyService.stats()["spans"]
# carries a snapshot (the benchmark deltas two of them), and /metrics
# exports the seconds.

span_seconds = Counter(
    "drand_span_seconds_total",
    "Host seconds inside each program span (metrics.span / metrics.add)",
    ["span"], registry=PRIVATE)

_spans: Dict[str, list] = {}     # name -> [count, seconds, prometheus child]
_spans_lock = threading.Lock()


def add(name: str, seconds: float = 0.0, count: int = 1) -> None:
    """Count `count` `name` events of `seconds` in all (0 for a plain
    count).  For an interval measured across threads; it writes no trace
    event."""
    with _spans_lock:
        t = _spans.get(name)
        if t is None:
            t = _spans[name] = [0, 0.0, None]
        t[0] += count
        t[1] += seconds
        if seconds > 0 and t[2] is None:
            t[2] = span_seconds.labels(
                registered_label(name, ns="span", limit=128))
        child = t[2]
    if child is not None and seconds > 0:
        child.inc(seconds)


def totals() -> Dict[str, List]:
    """Snapshot: {name: [count, seconds]}."""
    with _spans_lock:
        return {k: [t[0], t[1]] for k, t in _spans.items()}


class span:
    """`with span(name, **ids):` adds the block's host time to `name`
    (see `add`).  Where jax is loaded it also opens a
    `jax.profiler.TraceAnnotation(name, **ids)`, so a profiled run shows
    the block on the device trace's clock; `ids` (a chunk's first round,
    a program flavour) link the spans of one piece of work there.  Never
    used inside a traced function."""

    __slots__ = ("name", "seconds", "_ann", "_t0")

    def __init__(self, name: str, **ids):
        self.name = name
        self.seconds = 0.0
        jax = sys.modules.get("jax")
        self._ann = jax.profiler.TraceAnnotation(name, **ids) \
            if jax is not None else None

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        add(self.name, self.seconds)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def scrape(which: str = "group") -> bytes:
    reg = {"private": PRIVATE, "http": HTTP, "group": GROUP,
           "client": CLIENT}[which]
    return generate_latest(reg)


def scrape_all() -> bytes:
    return b"".join(generate_latest(r)
                    for r in (PRIVATE, HTTP, GROUP, CLIENT))


class ThresholdMonitor:
    """Escalating alerts when partial-send failures approach the threshold
    (metrics/threshold_monitor.go:12-105)."""

    def __init__(self, beacon_id: str, log: Logger, threshold: int,
                 period: float = 60.0):
        self.beacon_id = beacon_id
        self.log = log
        self.threshold = threshold
        self.period = period
        self._failed: Dict[str, bool] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name=f"thr-mon-{self.beacon_id}")
            self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.period):
            with self._lock:
                failing = sorted(self._failed)
                self._failed = {}
                thr = self.threshold
            if len(failing) >= thr:
                self.log.error("failed connections crossed threshold in the "
                               "last minute", threshold=thr,
                               failures=len(failing), nodes=",".join(failing))
            elif len(failing) >= thr // 2:
                self.log.warn("failed connections crossed half threshold in "
                              "the last minute", threshold=thr,
                              failures=len(failing), nodes=",".join(failing))

    def report_failure(self, addr: str) -> None:
        # committee peers are bounded by the group file, but addresses
        # churn across reshares — cap the series set regardless
        error_sending_partial.labels(
            self.beacon_id,
            registered_label(addr, ns="peer-address", limit=256)).inc()
        with self._lock:
            self._failed[addr] = True

    def update_threshold(self, new_threshold: int) -> None:
        with self._lock:
            self.threshold = new_threshold

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None


class MetricsServer:
    """Plain-HTTP metrics endpoint with profiling and peer federation
    (metrics.go:365-399).

    Routes: `/metrics` (all registries), `/metrics/<registry>`,
    `/debug/gc` (manual GC trigger, metrics.go:390-393), `/debug/pprof`
    (thread stack dump — Python's nearest pprof analogue), and
    `/peer/<addr>/metrics` when a peer-handler is installed."""

    def __init__(self, port: int, host: str = "127.0.0.1",
                 peer_metrics: Optional[Callable[[str], bytes]] = None):
        import http.server

        self.peer_metrics = peer_metrics
        outer = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                try:
                    body, ctype = outer._route(self.path)
                except KeyError:
                    self.send_error(404)
                    return
                except Exception as e:   # peer unreachable etc.
                    self.send_error(502, explain=str(e))
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.end_headers()
                self.wfile.write(body)

        self.httpd = http.server.ThreadingHTTPServer((host, port), Handler)
        self.port = self.httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def _route(self, path: str):
        text = "text/plain; version=0.0.4"
        if path == "/metrics":
            return scrape_all(), text
        if path.startswith("/metrics/"):
            return scrape(path.split("/", 2)[2]), text
        if path == "/debug/gc":
            import gc
            gc.collect()
            return b"GC run\n", "text/plain"
        if path == "/debug/pprof":
            import sys
            import traceback
            frames = sys._current_frames()
            out = []
            for tid, frame in frames.items():
                out.append(f"Thread {tid}:\n"
                           + "".join(traceback.format_stack(frame)))
            return "\n".join(out).encode(), "text/plain"
        if path.startswith("/peer/") and path.endswith("/metrics") \
                and self.peer_metrics is not None:
            addr = path[len("/peer/"):-len("/metrics")]
            return self.peer_metrics(addr), text
        raise KeyError(path)

    def start(self) -> None:
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True, name="metrics-http")
        self._thread.start()

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=2)
            self._thread = None
