#!/usr/bin/env python3
"""Chip benchmark of the edge-cloud speculative-decoding server.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on: the
cell's configuration (bench/configs/), traffic (bench/traffic/) and
serving settings (bench/workloads/) are found by name.  Set-up builds
the models from the seed and warms every program; the window then lasts
``--seconds`` of wall time; afterwards the audited rounds are compared
with the plain reference.  With ``--trace 0`` the result line carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
(a profiler trace of the window's first seconds).  Every metric is read
by bench/metrics/<name>.py.  The last stdout line is the result JSON;
the last stderr lines are the numbers compared, each beside its limit.
Exits nonzero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        sys.exit(f"bench/run.py: this cell needs {n} TPU chip(s); JAX "
                 f"found {len(devs)} {devs[0].platform!r} device(s) "
                 f"({devs[0].device_kind}) and the benchmark never falls "
                 f"back to them")
    return devs


def main(argv=None):
    args = parse(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness import cell as cell_mod
    spec = cell_mod.load(ROOT, args.workload)
    devs = require_chips(spec["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    result, checks = cell_mod.run(spec, args.seed, args.seconds,
                                  bool(args.trace), T_START, devs)
    cell_mod.emit(result, checks)


if __name__ == "__main__":
    main()
