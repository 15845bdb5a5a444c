"""Benchmark entry point: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Prints ``name,us_per_call,derived`` CSV to stdout (one line per benchmark
row) and writes the full per-figure CSVs to experiments/bench/.  A
benchmark that raises prints an ERROR row, the rest still run, and the
process exits 1.
"""
from __future__ import annotations

import argparse
import sys
import time


def _rows_to_csv(name, rows, latency_key, derived_key, scale=1e6):
    out = []
    for r in rows:
        us = float(r.get(latency_key, float("nan"))) * scale
        tag = "_".join(str(r.get(k, "")) for k in
                       ("method", "detail", "param", "temperature", "check",
                        "vocab", "name", "eta", "K", "B", "V", "arch",
                        "shape", "ell", "draft", "policy", "rate_rps")
                       if k in r)
        out.append(f"{name}[{tag}],{us:.1f},{r.get(derived_key, '')}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced grids (CI-friendly)")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    q = args.quick

    benches = []

    def reg(name, fn):
        if not args.only or args.only in name:
            benches.append((name, fn))

    from benchmarks import (bits_table, draft_scale, ell_resolution,
                            fig2_temperature, fig4_hparams, fig5_adaptivity,
                            fig6_compare, thm_checks)

    reg("fig2_temperature", lambda: _rows_to_csv(
        "fig2", fig2_temperature.run(q)[0], "latency_per_batch_s",
        "resampling_rate"))
    reg("fig4_hparams", lambda: _rows_to_csv(
        "fig4", fig4_hparams.run(q)[0], "latency_per_batch_s",
        "resampling_rate"))
    reg("fig5_adaptivity", lambda: _rows_to_csv(
        "fig5", fig5_adaptivity.run(q)[0], "latency_per_batch_s",
        "resampling_rate"))
    reg("fig6_compare", lambda: _rows_to_csv(
        "fig6", fig6_compare.run(q)[0], "latency_per_batch_s",
        "bits_per_batch"))
    reg("bits_table", lambda: _rows_to_csv(
        "bits", bits_table.run(q)[0], "bits_per_token", "vs_uncompressed",
        scale=1.0))
    reg("thm_checks", lambda: _rows_to_csv(
        "thm", thm_checks.run(q)[0], "measured", "holds", scale=1.0))
    reg("ell_resolution", lambda: _rows_to_csv(
        "ell", ell_resolution.run(q)[0], "latency_per_batch_s",
        "resampling_rate"))
    reg("draft_scale", lambda: _rows_to_csv(
        "draft", draft_scale.run(q)[0], "latency_per_batch_s",
        "accept_rate"))

    print("name,us_per_call,derived")
    failed = []
    for name, fn in benches:
        t0 = time.time()
        try:
            for line in fn():
                print(line, flush=True)
            print(f"# {name} done in {time.time()-t0:.0f}s",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — report, run the rest
            print(f"{name},nan,ERROR:{type(e).__name__}:{e}", flush=True)
            failed.append(name)
    if failed:
        print(f"# failed: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
