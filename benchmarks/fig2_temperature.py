"""Paper Fig. 2: average end-to-end latency and resampling rate for K-SQS
vs C-SQS across sampling temperatures.  Claim to validate: K-SQS wins at
low temperature; C-SQS wins (lower latency / resampling) at high
temperature — a crossover."""
from __future__ import annotations

from repro.core import MethodConfig

from benchmarks import common

TEMPS = [0.2, 0.5, 0.8, 1.0, 1.3]
KEYS = ["method", "temperature", "latency_per_batch_s", "resampling_rate",
        "accept_rate", "bits_per_batch", "mean_K", "tokens_per_batch"]


def run(quick: bool = False):
    dc, dp, tc, tp, data = common.trained_pair()
    temps = TEMPS[1:4] if quick else TEMPS
    rows = []
    for method in [MethodConfig("ksqs", K=16, ell=100),
                   MethodConfig("csqs", ell=100, alpha=5e-4, eta=1e-3)]:
        for T in temps:
            _, s = common.run_engine(dc, dp, tc, tp, data, method=method,
                                     temperature=T,
                                     rounds=4 if quick else None
                                     or common.BENCH_ROUNDS)
            rows.append({"method": method.name, "temperature": T, **{
                k: s[k] for k in KEYS[2:]}})
    path = common.emit_csv("fig2_temperature", rows, KEYS)
    return rows, path


def main():
    rows, path = run()
    for r in rows:
        print(f"{r['method']:5s} T={r['temperature']:.1f} "
              f"lat={r['latency_per_batch_s']*1e3:7.1f}ms "
              f"resample={r['resampling_rate']:.3f} "
              f"bits={r['bits_per_batch']:8.0f} K={r['mean_K']:6.1f}")
    print("->", path)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
