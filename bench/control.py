#!/usr/bin/env python3
"""Readings that set the limits of `correct`, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,... \
        --control-seeds 11,12,13 --quant int8 --seconds 51

For every seed it runs the cell as ``bench/run.py`` does (a window at
the cell's own load) and prints the numbers the comparison produced:
those are the program's readings, the lower end of each limit.  For the
control seeds it also puts the reference at the lower weight precision
``--quant`` in the program's place, at the same positions of the same
prompts and tokens, reads it by the same numbers and judges it by the
same verdict: the control's readings, the upper end, and whether the
control comes out not correct.  The benchmark's own runs never run
this.  The last stdout line is a JSON summary of every reading.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--quant", default="int8",
                    help="int8 (the control), fp8, or both comma-separated")
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args()
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import run as run_mod
    from harness import cell as cell_mod
    spec = cell_mod.load(ROOT, args.workload)
    devs = run_mod.require_chips(spec["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ctl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res, rows = cell_mod.run(spec, seed, args.seconds, False, t0, devs,
                                 quant=args.quant.split(",")
                                 if seed in ctl_seeds else ())
        nums = res.pop("_numbers")
        line = {"seed": seed, "correct": res["correct"], "numbers": nums,
                "control": res.get("control"),
                "metrics": res["metrics"],
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                "wall_s": time.perf_counter() - t0}
        out.append(line)
        print("READING " + json.dumps(line, default=float), flush=True)
    for name in ("target_gap", "draft_gap", "target_tv", "draft_tv"):
        prog = [r["numbers"][name] for r in out]
        print(f"{name}: program max {max(prog):.6g} over {len(prog)} seeds",
              flush=True)
        for mode in args.quant.split(","):
            ctl = [r["control"][mode][name] for r in out if r["control"]]
            if ctl:
                print(f"{name}: control {mode} min {min(ctl):.6g} over "
                      f"{len(ctl)} seeds", flush=True)
    for mode in args.quant.split(","):
        verdicts = [r["control"][mode]["correct"] for r in out
                    if r["control"]]
        print(f"control {mode}: correct {verdicts}", flush=True)
    print(json.dumps({"workload": args.workload, "quant": args.quant,
                      "readings": out}, default=float), flush=True)


if __name__ == "__main__":
    main()
