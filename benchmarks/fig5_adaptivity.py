"""Paper Fig. 5: benefit of adaptivity — C-SQS with η > 0 vs frozen
threshold (η = 0), across temperatures and initial β.  Claim: adaptive
updates yield lower latency and resampling, especially for small β₀."""
from __future__ import annotations

from repro.core import MethodConfig

from benchmarks import common

TEMPS = [0.5, 1.0, 1.3]
BETAS = [1e-3, 2e-2]
KEYS = ["eta", "beta0", "temperature", "latency_per_batch_s",
        "resampling_rate", "bits_per_batch", "mean_K"]


def run(quick: bool = False):
    dc, dp, tc, tp, data = common.trained_pair()
    temps = TEMPS[1:2] if quick else TEMPS
    rows = []
    for b0 in (BETAS[:1] if quick else BETAS):
        for eta in [0.0, 1e-3]:
            for T in temps:
                m = MethodConfig("csqs", beta0=b0, eta=eta, alpha=5e-4)
                _, s = common.run_engine(dc, dp, tc, tp, data, method=m,
                                         temperature=T)
                rows.append({"eta": eta, "beta0": b0, "temperature": T,
                             **{k: s[k] for k in KEYS[3:]}})
    path = common.emit_csv("fig5_adaptivity", rows, KEYS)
    return rows, path


def main():
    rows, path = run()
    for r in rows:
        print(f"eta={r['eta']:<6g} b0={r['beta0']:<6g} "
              f"T={r['temperature']:.1f} "
              f"lat={r['latency_per_batch_s']*1e3:7.1f}ms "
              f"resample={r['resampling_rate']:.3f}")
    print("->", path)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
