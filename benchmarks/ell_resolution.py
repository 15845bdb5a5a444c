"""Extra ablation (beyond the paper's figures): resampling rate and bits
vs lattice resolution ℓ — the K/(4ℓ) term of Theorem 1 predicts the
rejection overhead added by quantization shrinks as 1/ℓ, while payload
bits grow ~ K·log2(ℓ/K).  This sweep traces that trade-off end-to-end."""
from __future__ import annotations

from repro.core import MethodConfig

from benchmarks import common

ELLS = [25, 50, 100, 400, 1600]
KEYS = ["ell", "resampling_rate", "accept_rate", "bits_per_batch",
        "latency_per_batch_s", "tokens_per_batch"]


def run(quick: bool = False):
    dc, dp, tc, tp, data = common.trained_pair()
    rows = []
    for ell in (ELLS[1:4] if quick else ELLS):
        _, s = common.run_engine(dc, dp, tc, tp, data,
                                 method=MethodConfig("ksqs", K=16, ell=ell),
                                 temperature=0.8)
        rows.append({"ell": ell, **{k: s[k] for k in KEYS[1:]}})
    path = common.emit_csv("ell_resolution", rows, KEYS)
    return rows, path


def main():
    rows, path = run()
    for r in rows:
        print(f"ell={r['ell']:<5d} resample={r['resampling_rate']:.3f} "
              f"accept={r['accept_rate']:.3f} "
              f"bits={r['bits_per_batch']:8.0f}")
    print("->", path)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
