"""Theorem 1 and Theorem 2 empirical validation on the trained pair.

Thm 1: measured resampled-token count  ≤  Σ TV(q,p) + Σ(α_n + K/(4ℓ)).
Thm 2: time-averaged dropped mass      ≤  α + (|β₁|+1+ηα)/(ηT).
"""
from __future__ import annotations

import numpy as np

from repro.core import MethodConfig, conformal
from repro.core.slq import tv_distance

from benchmarks import common

KEYS = ["check", "temperature", "measured", "bound", "holds"]


def run(quick: bool = False):
    dc, dp, tc, tp, data = common.trained_pair()
    rows = []
    for T in ([1.0] if quick else [0.5, 1.0]):
        # ---- Theorem 1 on K-SQS ----
        m = MethodConfig("ksqs", K=16, ell=100)
        rounds, s = common.run_engine(dc, dp, tc, tp, data, method=m,
                                      temperature=T, collect_theory=True,
                                      warmup=0)
        measured = float(np.sum([r["rejected"].mean() for r in rounds]))
        bound = 0.0
        import jax.numpy as jnp
        for r in rounds:
            q, p, qh = r["q"], r["p"], r["q_hat"]        # (B,L,V),(B,L+1,V)
            L = q.shape[1]
            live = np.arange(L)[None] < r["L_live"][:, None]
            mism = np.asarray(tv_distance(jnp.asarray(q),
                                          jnp.asarray(p[:, :L])))
            terms = (mism + r["dropped_seq"][:, :L]
                     + r["K_seq"] / (4.0 * m.ell)) * live
            # per-round rejected-and-resampled is at most 1; the bound sums
            # per-token rejection probabilities of live tokens
            bound += float(terms.sum(1).mean())
        rows.append({"check": "thm1_ksqs", "temperature": T,
                     "measured": measured, "bound": bound,
                     "holds": int(measured <= bound + 1e-6)})
        # ---- Theorem 2 on C-SQS ----
        mc = MethodConfig("csqs", alpha=5e-4, eta=1e-3, beta0=1e-3)
        rounds, s = common.run_engine(dc, dp, tc, tp, data, method=mc,
                                      temperature=T, collect_theory=True,
                                      warmup=0)
        drops = np.concatenate([r["dropped_seq"].ravel() for r in rounds])
        Tn = drops.size
        avg = float(drops.mean())
        b2 = float(conformal.thm2_bound(mc.alpha, mc.eta, mc.beta0, Tn))
        rows.append({"check": "thm2_csqs", "temperature": T,
                     "measured": avg, "bound": b2,
                     "holds": int(avg <= b2 + 1e-9)})
    path = common.emit_csv("thm_checks", rows, KEYS)
    return rows, path


def main():
    rows, path = run()
    for r in rows:
        print(f"{r['check']:10s} T={r['temperature']:.1f} "
              f"measured={r['measured']:.4f} bound={r['bound']:.4f} "
              f"holds={bool(r['holds'])}")
    print("->", path)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
