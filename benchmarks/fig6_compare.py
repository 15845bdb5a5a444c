"""Paper Fig. 6: K-SQS (several K) vs C-SQS overlay across temperature —
plus the baselines the paper builds on: dense QS [22] and uncompressed SD.
"""
from __future__ import annotations

from repro.core import MethodConfig

from benchmarks import common

TEMPS = [0.3, 0.8, 1.3]
KEYS = ["method", "detail", "temperature", "latency_per_batch_s",
        "resampling_rate", "accept_rate", "bits_per_batch"]


def run(quick: bool = False):
    dc, dp, tc, tp, data = common.trained_pair()
    temps = TEMPS[1:2] if quick else TEMPS
    methods = [
        ("ksqs-8", MethodConfig("ksqs", K=8)),
        ("ksqs-64", MethodConfig("ksqs", K=64)),
        ("csqs", MethodConfig("csqs")),
        ("qs-dense", MethodConfig("qs")),
        ("uncompressed", MethodConfig("uncompressed")),
    ]
    if quick:
        methods = methods[1:4]
    rows = []
    for name, m in methods:
        for T in temps:
            _, s = common.run_engine(dc, dp, tc, tp, data, method=m,
                                     temperature=T)
            rows.append({"method": m.name, "detail": name,
                         "temperature": T, **{k: s[k] for k in KEYS[3:]}})
    path = common.emit_csv("fig6_compare", rows, KEYS)
    return rows, path


def main():
    rows, path = run()
    for r in rows:
        print(f"{r['detail']:13s} T={r['temperature']:.1f} "
              f"lat={r['latency_per_batch_s']*1e3:7.1f}ms "
              f"resample={r['resampling_rate']:.3f} "
              f"bits={r['bits_per_batch']:9.0f}")
    print("->", path)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
