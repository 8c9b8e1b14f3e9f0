#!/usr/bin/env python
"""Benchmark entry: one run of one cell on the chip this process holds.

    python benchmark/run.py --workload quicknet.scan --seed 7 \
        --seconds 30 --trace 0

Cells, configurations, traffic mixes and metrics are named in
`BENCHMARK.json` and found as files under `benchmark/` (harness/spec.py).
The run fails, and prints no result, unless JAX's first device is a TPU
and it sees as many as the cell asks for.  The last line of standard
output is the result object; the compared numbers and their limits are
the last lines of standard error and the last key of the result.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)

# libtpu logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, CHECKOUT)
    sys.path.insert(0, HERE)
    from harness.cell import log, run_cell
    from harness.spec import Spec

    spec = Spec()
    cell = spec.workload(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        log(f"no chip: JAX sees {len(devs)} {devs[0].platform} device(s) "
            f"({devs[0].device_kind}); {args.workload} needs "
            f"{cell['chips']} TPU chip(s)")
        return 2
    from drand_tpu import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    result = run_cell(spec, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START)
    for name, c in result["checks"].items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
