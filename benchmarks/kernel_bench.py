"""Kernel microbenchmark: fused Pallas SQS path vs the stock-jnp path.

On this CPU container the Pallas kernel runs in interpret mode (Python),
so wall-clock favours the XLA-compiled jnp path — the meaningful derived
number here is the analytic HBM-traffic model (sweeps over the (B, V)
tensor), which is what decides on TPU.  Wall times are still reported for
the jnp path and the oracle, per table row.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sqs as core_sqs
from repro.kernels import ops

KEYS = ["name", "B", "V", "us_per_call", "hbm_sweeps_model"]


def _time(fn, *args, reps=5):
    fn(*args)                          # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run(quick: bool = False):
    rows = []
    shapes = [(4, 50257)] if quick else [(1, 50257), (8, 50257),
                                         (4, 152064)]
    for B, V in shapes:
        logits = jax.random.normal(jax.random.PRNGKey(0), (B, V)) * 3.0
        beta = jnp.full((B,), 1e-3)

        def jnp_threshold(lg, b):
            q = core_sqs.softmax_temp(lg, 1.0)
            return core_sqs.sparsify_threshold(q, b[:, None], 100)

        def jnp_topk(lg):
            q = core_sqs.softmax_temp(lg, 1.0)
            return core_sqs.sparsify_topk(q, 64, 100)

        t1 = _time(jax.jit(jnp_threshold), logits, beta)
        t2 = _time(jax.jit(jnp_topk), logits)
        # jnp path: softmax (2 sweeps) + mask/renorm (2) + quantize w/ two
        # argsorts (~4) ≈ 8 HBM sweeps of (B,V); fused kernel: 1 read +
        # 1 write ≈ 2 sweeps.
        rows += [
            {"name": "jnp_threshold_sqs", "B": B, "V": V,
             "us_per_call": t1, "hbm_sweeps_model": 8.0},
            {"name": "jnp_topk_sqs", "B": B, "V": V,
             "us_per_call": t2, "hbm_sweeps_model": 9.0},
            {"name": "pallas_sqs_fused(target)", "B": B, "V": V,
             "us_per_call": float("nan"), "hbm_sweeps_model": 2.0},
        ]
        if B <= 4 and quick is False:
            t3 = _time(lambda lg, b: ops.sqs_threshold(lg, b, ell=100),
                       logits, beta)
            rows.append({"name": "pallas_interpret_threshold", "B": B,
                         "V": V, "us_per_call": t3,
                         "hbm_sweeps_model": 2.0})
    from benchmarks import common
    path = common.emit_csv("kernel_bench", rows, KEYS)
    return rows, path


def main():
    rows, path = run()
    for r in rows:
        print(f"{r['name']:28s} B={r['B']:<3d} V={r['V']:<7d} "
              f"{r['us_per_call']:10.1f} us/call  "
              f"~{r['hbm_sweeps_model']:.0f} HBM sweeps")
    print("->", path)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
