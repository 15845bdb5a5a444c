"""Extra ablation: draft-model capacity vs acceptance/latency — the
SLM↔LLM *mismatch* term of Theorem 1 is the one knob the compression
method cannot touch; this sweep isolates it (same target, drafts at 2x/4x
reduction and an untrained control)."""
from __future__ import annotations

import jax

from repro import configs
from repro.core import MethodConfig
from repro.models import init_params

from benchmarks import common

KEYS = ["draft", "accept_rate", "resampling_rate", "tokens_per_batch",
        "latency_per_batch_s"]


def run(quick: bool = False):
    dc, dp, tc, tp, data = common.trained_pair()
    drafts = {"trained-2x": (dc, dp)}
    if not quick:
        dc4 = configs.draft_variant(tc, 4)
        dp4, _ = common._train(dc4, common.BENCH_STEPS // 2, 9, data)
        drafts["trained-4x"] = (dc4, dp4)
        drafts["untrained-2x"] = (dc, init_params(
            dc, jax.random.PRNGKey(99)))
        drafts["self(target)"] = (tc, tp)
    rows = []
    for name, (dcfg, dpar) in drafts.items():
        _, s = common.run_engine(dcfg, dpar, tc, tp, data,
                                 method=MethodConfig("ksqs", K=32),
                                 temperature=0.8)
        rows.append({"draft": name, **{k: s[k] for k in KEYS[1:]}})
    path = common.emit_csv("draft_scale", rows, KEYS)
    return rows, path


def main():
    rows, path = run()
    for r in rows:
        print(f"{r['draft']:16s} accept={r['accept_rate']:.3f} "
              f"resample={r['resampling_rate']:.3f} "
              f"tokens/batch={r['tokens_per_batch']:.2f}")
    print("->", path)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
