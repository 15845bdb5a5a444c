#!/usr/bin/env python3
"""Arrival-rate sweep of one cell on the chip, in one process.

    python3 bench/knee.py --workload <cell> --rates 0.5,1,2 --seconds 40 \
        [--slots 16]

Runs the cell once per rate (the traffic file's ``rate_rps`` replaced,
and with ``--slots`` the slot count and the opening burst), each with
the same seed, and prints for each the serving-clock span of
the window, arrivals, completions and the waiting queue at open and at
close.  The knee is the highest rate at which the queue does not grow
over the window; the cell's traffic file then states about four fifths
of it.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import copy
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=424242)
    ap.add_argument("--slots", type=int, default=0)
    args = ap.parse_args()
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import run as run_mod
    from harness import cell as cell_mod
    base = cell_mod.load(ROOT, args.workload)
    devs = run_mod.require_chips(base["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        spec = copy.deepcopy(base)
        spec["traffic"]["rate_rps"] = rate
        if args.slots:
            spec["cell"]["slots"] = spec["traffic"]["burst"] = args.slots
        print(f"RATE {rate}", flush=True)
        res, _ = cell_mod.run(spec, args.seed, args.seconds, False,
                              time.perf_counter(), devs, compare=False)
        res.pop("_numbers")
        print(f"RESULT rate {rate} metrics "
              f"{res['metrics']}", flush=True)


if __name__ == "__main__":
    main()
