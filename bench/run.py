#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration, ``bench/configs/<config>.json``, and a traffic mix,
``bench/traffic/<mix>.json``.  The run builds the configuration's store
from the seed, serves it through the program's async tier on a loop
thread of this process, warms it up, and drives the mix against it over
HTTP ``/v1`` for ``--seconds``.  Every answer of the window is then
compared with a plain full-scan reference.

Standard error carries the set-up phases, the window's counts and how
late the generator ran, and, last, each number compared beside its limit.
The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics, each read by
``bench/layers/<metric>.py`` from a profiled window), ``device`` and the
compared numbers under ``checks``.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, when the device is missing from
``bench/peaks.json``, or when the program is not beside the benchmark.

Two more modes serve the measurements behind the benchmark's limits and
rates, and print their own lines:

* ``--control`` reads the control (the reference in bfloat16 put in the
  program's place) on each seed of ``--seed a,b,c``;
* ``--sweep r1,r2,...`` serves open-loop windows at each offered rate
  after one set-up, and prints the completions per rate (the knee).
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from mbench import cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True,
                    help="a whole number (a list a,b,c with --control)")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--sweep", default="",
                    help="offered rates, requests/s, for a knee sweep")
    args = ap.parse_args(argv)
    paths = cell.Paths(os.path.dirname(BENCH))
    try:
        if args.control:
            out = cell.control(paths, args.workload,
                               cell.parse_seeds(args.seed), args.seconds)
            print(json.dumps({"control": out}), flush=True)
            return 0
        if args.sweep:
            from mbench import sweep
            out = sweep.run(paths, args.workload, int(args.seed),
                            args.seconds,
                            [float(r) for r in args.sweep.split(",")])
            print(json.dumps({"sweep": out}), flush=True)
            return 0
        result = cell.run(paths, args.workload, int(args.seed), args.seconds,
                          bool(args.trace), process_start=PROCESS_START)
    except (cell.NoDevice, cell.BadSetup) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
