#!/usr/bin/env python
"""Lane-width x pipeline-depth autotune sweep (ISSUE 10, ROADMAP item 2).

Sequential scan stages (the 758-step E2 pow, the GLV ladders) cost per
STEP, not per lane, so wider pads amortize them — and a depth-k dispatch
window amortizes the per-dispatch latency.  Which (pad, depth)
point wins depends on the accelerator, so it is MEASURED, not assumed:
this tool sweeps pad x depth per scheme kind on the current backend,
streams a signed fixture through `BatchBeaconVerifier.verify_stream`,
and persists the winner to TUNING.json — which the resident verify
service consults at handle creation (crypto/tuning.py; env overrides
win; a container with no chip and no tuning file is unchanged).

    python tools/autotune.py                      # full sweep -> TUNING.json
    python tools/autotune.py --pads 8192,16384,32768 --depths 1,2,4
    python tools/autotune.py --selftest           # tiny CPU sweep into a
                                                  # temp file + proof the
                                                  # service consults it

The full sweep is sized for a chip round (pad 32768 x G2 is hours of
compile on a cold CPU cache); the driver runs it once per chip round,
after bench.py has pre-warmed the compilation cache.

Every measured (kind, group, pad, depth) cell commits the best-so-far
winners to TUNING.json before the next cell starts, so a later hang never
discards finished measurements.
"""
# tpu-vet: disable-file=verifier  (the sweep MEASURES raw
# BatchBeaconVerifier configs to pick what the service will use)

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KIND_SCHEMES = {"g1": "bls-unchained-on-g1", "g2": "pedersen-bls-unchained"}


def _fixture(kind, n):
    """n device-signed unchained beacons for the kind's scheme."""
    from drand_tpu.chain.beacon import Beacon
    from drand_tpu.crypto import batch, schemes

    sch = schemes.scheme_from_name(KIND_SCHEMES[kind])
    sec, pub = sch.keypair(seed=b"autotune-" + kind.encode())
    msgs = [sch.digest_beacon(r, None) for r in range(1, n + 1)]
    sigs = batch.sign_batch(sch, sec, msgs)
    beacons = [Beacon(round=r, signature=s)
               for r, s in zip(range(1, n + 1), sigs)]
    return sch, sch.public_bytes(pub), beacons


def _group_devices(group_size):
    """The first `group_size` devices of the pool inventory — the device
    group a sweep at that size measures on (None = default placement)."""
    if group_size <= 1:
        return None
    from drand_tpu.crypto.device_pool import jax_devices

    devs = jax_devices()
    if len(devs) < group_size:
        return None
    return devs[:group_size]


def _measure(sch, pub, beacons, pad, depth, group_size=1):
    """Warm rounds/s of one streamed pass at (pad, depth) on a
    `group_size`-device group."""
    from drand_tpu.crypto import batch

    ver = batch.BatchBeaconVerifier(sch, pub, pad_to=pad,
                                    devices=_group_devices(group_size))

    def replay():
        n = 0
        for rounds, ok in ver.verify_stream(iter(beacons), chunk_size=pad,
                                            depth=depth):
            assert ok.all(), "autotune fixture failed verification"
            n += len(rounds)
        return n

    replay()                                  # cold: compile/cache-load
    t0 = time.perf_counter()
    n = replay()
    dt = time.perf_counter() - t0
    return n / dt, ver.pipeline_depth(depth, pad)


def sweep(kinds, pads, depths, n, progress=lambda m: None,
          group_sizes=(1,), commit=lambda winners, rows: None):
    """-> (winners {tuning key: entry}, rows [sweep table]).

    Winners are keyed per GROUP SIZE (ISSUE 11): the bare kind for a
    1-device group (the legacy spelling crypto/tuning.py falls back to)
    and `<kind>@<n>` for an n-device group, so a 1-device and a 4-device
    group never share a TUNING.json winner.

    `commit(winners, rows)` fires after EVERY measured cell with the
    best-so-far winner tables — a later cell that hangs past the driver
    budget loses only itself, never the measurements already taken."""
    rows = []
    winners = {}
    for kind in kinds:
        nn = max(n, 2 * max(pads))            # >= 2 chunks at the widest pad
        progress(f"fixture {kind}: signing {nn} rounds")
        sch, pub, beacons = _fixture(kind, nn)
        for gs in group_sizes:
            if gs > 1 and _group_devices(gs) is None:
                progress(f"{kind}@{gs}: fewer than {gs} devices, skipped")
                continue
            best = None
            entry_key = kind if gs == 1 else f"{kind}@{gs}"
            for pad in pads:
                for depth in depths:
                    progress(f"{kind}@{gs} pad={pad} depth={depth}")
                    rps, eff_depth = _measure(sch, pub, beacons, pad,
                                              depth, group_size=gs)
                    row = {"kind": kind, "group_size": gs, "pad": pad,
                           "depth": depth, "effective_depth": eff_depth,
                           "rounds_per_s": round(rps, 1)}
                    rows.append(row)
                    progress(f"{kind}@{gs} pad={pad} depth={depth}: "
                             f"{rps:.1f} r/s")
                    if best is None or rps > best["rounds_per_s"]:
                        best = row
                        winners[entry_key] = {
                            "pad": best["pad"], "depth": best["depth"],
                            "rounds_per_s": best["rounds_per_s"]}
                    commit(winners, rows)
    return winners, rows


def _selftest(args):
    """Tiny CPU-scale sweep into a temp TUNING.json, then prove the
    verify service CONSULTS it: a fresh service (pad=0 auto) must resolve
    the written winner for a new handle (the ISSUE acceptance)."""
    import jax

    from drand_tpu.crypto import schemes, tuning
    from drand_tpu.crypto.verify_service import VerifyService

    # explicit env overrides would (correctly) beat the file — clear them
    # so the selftest exercises the TUNING.json leg of the precedence
    for var in ("DRAND_VERIFY_PAD", "DRAND_VERIFY_PIPELINE_DEPTH"):
        os.environ.pop(var, None)
    out = args.out or os.path.join(
        tempfile.mkdtemp(prefix="drand_tpu_autotune_"), "TUNING.json")
    platform = jax.default_backend()
    winners, rows = sweep(["g1"], [32, 64], [1, 2], 128,
                          progress=lambda m: print(f"# {m}", file=sys.stderr,
                                                   flush=True))
    tuning.write_tuning(out, platform, winners)
    os.environ["DRAND_TUNING_FILE"] = out

    sch = schemes.scheme_from_name(KIND_SCHEMES["g1"])
    _, pub = sch.keypair(seed=b"autotune-consult")
    svc = VerifyService(pad=0)                # AUTO: must consult the file
    try:
        h = svc.handle(sch, sch.public_bytes(pub), device=True)
        got = next(iter(svc.stats()["tuning"].values()))
        want = winners["g1"]
        consulted = (got["pad"] == want["pad"]
                     and got["depth"] == want["depth"]
                     and getattr(h.backend, "pad_to", None) == want["pad"])
        report = {"ok": bool(consulted), "platform": platform,
                  "tuning_file": out, "winner": want, "consulted": got,
                  "sweep": rows}
        print(json.dumps(report), flush=True)
        return 0 if consulted else 1
    finally:
        svc.stop()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--pads", default="8192,16384,32768")
    ap.add_argument("--depths", default="1,2,4")
    ap.add_argument("--kinds", default="g1,g2")
    ap.add_argument("--group-sizes", default="",
                    help="device-group sizes to sweep (comma list; "
                         "default: 1, plus the full pool when more than "
                         "one device is visible)")
    ap.add_argument("--n", type=int, default=0,
                    help="fixture rounds (default: 2x the widest pad)")
    ap.add_argument("--out", default=None,
                    help="TUNING.json path (default: repo root; selftest: "
                         "a fresh temp file)")
    ap.add_argument("--selftest", action="store_true",
                    help="tiny CPU sweep + proof the service consults "
                         "the result (exit 0/1)")
    args = ap.parse_args(argv)
    from drand_tpu import compile_cache
    compile_cache.enable()
    if args.selftest:
        return _selftest(args)

    import jax
    platform = jax.default_backend()
    pads = [int(x) for x in args.pads.split(",") if x.strip()]
    depths = [int(x) for x in args.depths.split(",") if x.strip()]
    kinds = [k.strip() for k in args.kinds.split(",") if k.strip()]
    for k in kinds:
        if k not in KIND_SCHEMES:
            ap.error(f"unknown kind {k!r} (have {sorted(KIND_SCHEMES)})")
    if args.group_sizes.strip():
        group_sizes = [int(x) for x in args.group_sizes.split(",")
                       if x.strip()]
    else:
        from drand_tpu.crypto.device_pool import jax_devices

        n_devs = len(jax_devices())
        group_sizes = [1] + ([n_devs] if n_devs > 1 else [])
    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "TUNING.json")
    from drand_tpu.crypto import tuning

    def commit(winners_so_far, rows_so_far):
        # every cell lands on disk before the next one starts: the winner
        # table goes straight into TUNING.json (atomic temp + rename) and
        # the raw sweep rows into a sidecar for postmortems of a killed run
        if winners_so_far:
            tuning.write_tuning(out, platform, winners_so_far)
        tmp = f"{out}.sweep.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"platform": platform, "complete": False,
                       "rows": rows_so_far}, f, indent=1)
            f.write("\n")
        os.replace(tmp, out + ".sweep.json")

    winners, rows = sweep(kinds, pads, depths, args.n,
                          progress=lambda m: print(f"# {m}", file=sys.stderr,
                                                   flush=True),
                          group_sizes=group_sizes, commit=commit)
    tuning.write_tuning(out, platform, winners)
    tmp = f"{out}.sweep.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"platform": platform, "complete": True, "rows": rows}, f,
                  indent=1)
        f.write("\n")
    os.replace(tmp, out + ".sweep.json")
    print(json.dumps({"ok": True, "platform": platform, "out": out,
                      "winners": winners, "sweep": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
