"""The program's span/counter registry (`metrics.span` / `add` /
`totals`) and the spans inside the scan and verify path.

The scan runs through a verify service whose device backend is
`BatchBeaconVerifier`'s own pack and resolve stages around a one-line
program in place of the RLC pass, so the whole path runs on the CPU and
tier-1 compiles no pairing program.
"""

import os
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from drand_tpu import metrics
from drand_tpu.chain.beacon import Beacon
from drand_tpu.chain.integrity import MODE_FULL, IntegrityScanner
from drand_tpu.chain.sqlitedb import SqliteStore
from drand_tpu.crypto import batch, schemes
from drand_tpu.crypto.host import serialize
from drand_tpu.crypto.host.params import G1_GEN
from drand_tpu.crypto.verify_service import VerifyService
from harness import OwnWork

SCHEME_ID = "bls-unchained-g1-rfc9380"
SPANS = ("integrity.read", "integrity.wait", "verify.queue", "verify.pack",
         "verify.dispatch", "verify.wait", "verify.return")


class OneLineProgram(batch.BatchBeaconVerifier):
    """The device backend with a one-line program for its RLC pass: every
    well-formed signature passes."""

    def __init__(self, scheme, public_key, name):
        super().__init__(scheme, public_key)
        self.program = jax.jit(lambda ok: jnp.all(ok))
        self.name = name

    def dispatch_packed(self, packed):
        _, _, bad, _ = packed
        return batch.run_program(self.program, jnp.asarray(~bad),
                                 name=self.name)


@pytest.fixture
def scan(tmp_path, monkeypatch):
    """-> run(name, scans): scan 20 stored rounds in chunks of 12,
    `scans` times, through a fresh service (programs of widths 12 and 8);
    returns the last scan's report, the span and counter deltas of this
    test's own work in it, and the service's stats."""
    sch = schemes.scheme_from_name(SCHEME_ID)
    pk = sch.public_bytes(sch.keypair(seed=b"spans")[1])
    store = SqliteStore(os.path.join(str(tmp_path), "chain.db"))
    sig = serialize.g1_to_bytes(G1_GEN)
    store.put_many([Beacon(round=r, signature=sig) for r in range(1, 21)])

    def run(name, scans=1):
        work = run.work = OwnWork(monkeypatch)
        svc = VerifyService(pad=16, pipeline_depth=1, background_window=0.0)
        try:
            handle = svc.handle(sch, pk, backend=OneLineProgram(sch, pk, name))
            for _ in range(scans):
                since = work.mark()
                report = IntegrityScanner(store, sch, verifier=handle,
                                          chunk=12).scan(mode=MODE_FULL)
            return report, work.delta(since), svc.stats()
        finally:
            svc.stop()

    yield run
    store.close()


def test_scan_spans_count_chunks_and_first_calls_per_flavour(scan):
    report, d, stats = scan("spans_test")
    assert report.scanned == 20 and not report.findings
    for name in SPANS:
        n, secs = d[name]
        assert n == 2, name                     # one per chunk
        assert secs > 0 or name == "verify.queue", name
    assert d["batch.dispatch"][0] == 2
    flavours = sorted(k for k in d if k.startswith("batch.first_call/")
                      and k.count("/") == 1)
    assert flavours == ["batch.first_call/spans_test@12",
                        "batch.first_call/spans_test@8"]
    assert d["batch.first_call"][0] == 2
    for f in flavours:
        assert d[f][0] == 1 and d[f][1] > 0
        assert d[f + "/trace"][0] >= 1          # jax events of that call
        assert d[f + "/compile"][1] > 0
    # the service carries the snapshot, and its pack term is that span's
    # process-wide seconds since the service started: this test's own
    # packs, and no more than every thread's since the scan began
    assert stats["spans"]["verify.pack"][0] >= 2
    every = scan.work.delta(own=False)["verify.pack"][1]
    assert d["verify.pack"][1] - 1e-9 <= stats["pack_time_s"] <= every + 1e-9


def test_a_second_scan_compiles_nothing(scan):
    _, d, _ = scan("spans_again", scans=2)
    assert d["batch.dispatch"][0] == 2 and d["verify.pack"][0] == 2
    assert d.get("batch.first_call", (0, 0.0))[0] == 0


def test_no_span_takes_a_name_the_benchmark_selects(scan):
    """The benchmark's trace reduction selects host events named
    `scan.verify` and `scan.outside`: no program span may take them."""
    scan("spans_names")
    names = set(metrics.totals())
    assert names.isdisjoint({"scan.verify", "scan.outside"})
    assert names >= set(SPANS)


def test_span_seconds_are_exported():
    with metrics.span("test.exported"):
        pass
    blob = metrics.scrape("private").decode()
    assert 'drand_span_seconds_total{span="test.exported"}' in blob


def test_counts_and_spans_add_up_under_threads():
    """More threads than cores on a shortened switch interval: no lost
    update in the registry."""
    threads, per = 2 * (os.cpu_count() or 2) + 2, 400
    name = "test.stress"
    before = metrics.totals().get(name, (0, 0.0))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                metrics.add(name, 0.5)
                with metrics.span(name):
                    pass
        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    n, secs = metrics.totals()[name]
    assert n - before[0] == 2 * threads * per
    assert secs - before[1] >= 0.5 * threads * per
