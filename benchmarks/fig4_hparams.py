"""Paper Fig. 4 ablation: latency vs K (K-SQS) and vs β₀ (C-SQS) across
temperatures."""
from __future__ import annotations

from repro.core import MethodConfig

from benchmarks import common

KS = [4, 16, 64, 256]
BETAS = [1e-4, 1e-3, 1e-2, 5e-2]
TEMPS = [0.5, 1.0]
KEYS = ["method", "param", "temperature", "latency_per_batch_s",
        "resampling_rate", "bits_per_batch", "mean_K"]


def run(quick: bool = False):
    dc, dp, tc, tp, data = common.trained_pair()
    ks = KS[1:3] if quick else KS
    bs = BETAS[1:3] if quick else BETAS
    temps = TEMPS[:1] if quick else TEMPS
    rows = []
    for T in temps:
        for K in ks:
            _, s = common.run_engine(dc, dp, tc, tp, data,
                                     method=MethodConfig("ksqs", K=K),
                                     temperature=T)
            rows.append({"method": "ksqs", "param": K, "temperature": T,
                         **{k: s[k] for k in KEYS[3:]}})
        for b0 in bs:
            _, s = common.run_engine(
                dc, dp, tc, tp, data,
                method=MethodConfig("csqs", beta0=b0), temperature=T)
            rows.append({"method": "csqs", "param": b0, "temperature": T,
                         **{k: s[k] for k in KEYS[3:]}})
    path = common.emit_csv("fig4_hparams", rows, KEYS)
    return rows, path


def main():
    rows, path = run()
    for r in rows:
        print(f"{r['method']:5s} p={r['param']:<8g} T={r['temperature']:.1f} "
              f"lat={r['latency_per_batch_s']*1e3:7.1f}ms "
              f"resample={r['resampling_rate']:.3f} "
              f"bits={r['bits_per_batch']:8.0f}")
    print("->", path)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
