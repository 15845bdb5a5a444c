"""Uplink bits-per-token accounting table (paper eqs. (1)/(2)/(5)) for the
paper's GPT-Neo vocabulary and every assigned architecture's vocabulary,
including the beyond-paper gap-coded subset encoding."""
from __future__ import annotations

import numpy as np

from repro import configs
from repro.core import bits

KEYS = ["vocab", "method", "K", "ell", "bits_per_token", "vs_uncompressed"]


def run(quick: bool = False):
    vocabs = {"gptneo(50257)": 50257}
    if not quick:
        for a in configs.ASSIGNED:
            c = configs.get_config(a)
            vocabs[f"{a}({c.vocab})"] = c.vocab
    rows = []
    ell = 100
    for name, V in vocabs.items():
        unc = bits.uncompressed_bits(V)
        entries = [
            ("uncompressed", 0, float(unc)),
            ("qs-dense", V, float(bits.dense_qs_bits(V, ell))),
            ("ksqs", 16, float(bits.token_bits(V, 16.0, ell, False))),
            ("ksqs", 64, float(bits.token_bits(V, 64.0, ell, False))),
            ("csqs", 64, float(bits.token_bits(V, 64.0, ell, True))),
            ("csqs", 256, float(bits.token_bits(V, 256.0, ell, True))),
        ]
        # gap coding on a frequency-sorted support (Zipf-realistic): top-K
        # ids with jitter
        rng = np.random.default_rng(0)
        for K in (16, 64):
            idx = np.unique(np.minimum(
                rng.zipf(1.3, K * 4), V - 1))[:K]
            mask = np.zeros((1, V), bool)
            mask[0, idx] = True
            import jax.numpy as jnp
            g = float(bits.gap_code_subset_bits(jnp.asarray(mask))[0]) + \
                float(bits.payload_bits(float(len(idx)), ell))
            entries.append((f"gap-coded-sqs", len(idx), g))
        for meth, K, b in entries:
            rows.append({"vocab": name, "method": meth, "K": K, "ell": ell,
                         "bits_per_token": b,
                         "vs_uncompressed": b / unc})
    from benchmarks import common
    path = common.emit_csv("bits_table", rows, KEYS)
    return rows, path


def main():
    rows, path = run()
    last = None
    for r in rows:
        if r["vocab"] != last:
            print(f"-- {r['vocab']}")
            last = r["vocab"]
        print(f"  {r['method']:14s} K={r['K']:<7d} "
              f"{r['bits_per_token']:12.1f} bits/token "
              f"({100*r['vs_uncompressed']:.3f}% of raw)")
    print("->", path)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
