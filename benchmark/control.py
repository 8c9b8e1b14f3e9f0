#!/usr/bin/env python
"""The check's control: the plain reference put in the program's place,
with one guarantee of the configuration broken.  Its run has to come out
not correct.

    JAX_PLATFORMS=cpu python benchmark/control.py --workload quicknet.scan \
        --control wrong_dst --seed 5 --seconds 10

The run is the cell's own (fixture, store, scan loop, window, check) at
the cell's size; only the verify call is replaced.  Controls:

  wrong_dst      verify under the other G1/G2 hash-to-curve domain (the
                 pre-RFC 9380 suite string): breaks "the chain's own DST"
  unchained      verify sha256(round) without the previous signature:
                 breaks "chained on the stored signature below"
  chunk_verdict  a failed chunk flags every round in it (no bisection to
                 the bad round): breaks "faulty iff its own check fails"
"""

import argparse
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def _rule(chain, **kw):
    c = copy.copy(chain)
    for k, v in kw.items():
        setattr(c, k, v)
    return c


def wrong_dst(fx):
    from harness import refbls
    other = refbls.DST_G2 if fx.chain.dst == refbls.DST_G1 else refbls.DST_G1
    return _rule(fx.chain, dst=other)


def unchained(fx):
    return _rule(fx.chain, chained=False)


CONTROLS = ("wrong_dst", "unchained", "chunk_verdict")


def make_wrap(name: str):
    """-> verify_wrap for `run_cell`: the reference, as the control."""
    def wrap(_verify_batch, fx):
        if name == "chunk_verdict":
            def verify(rounds, sigs, prevs=None):
                prevs = prevs or [None] * len(rounds)
                ok = fx.chain.verify_many(zip(rounds, prevs, sigs))
                return [all(ok)] * len(ok)
            return verify
        chain = {"wrong_dst": wrong_dst, "unchained": unchained}[name](fx)

        def verify(rounds, sigs, prevs=None):
            prevs = prevs or [None] * len(rounds)
            return chain.verify_many(zip(rounds, prevs, sigs))
        return verify
    return wrap


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True, choices=CONTROLS)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(HERE))
    sys.path.insert(0, HERE)
    from harness.cell import log, run_cell
    from harness.spec import Spec
    t0 = time.monotonic()
    result = run_cell(Spec(), args.workload, args.seed, args.seconds, False,
                      t0, device=False, verify_wrap=make_wrap(args.control))
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(dict(result, control=args.control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
