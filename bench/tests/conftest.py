"""Test helpers: the harness on the CPU at a tiny size.  These tests
skip the harness's look for a chip and drive the rest of a run."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

TINY = {"name": "tiny", "family": "dense", "n_layers": 2, "d_model": 128,
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
        "vocab": 512, "rope_theta": 10000.0, "rms_eps": 1e-6,
        "qkv_bias": True, "tie_embeddings": True, "dtype": "bfloat16"}


def tiny_spec(method="csqs", slots=3, seconds_trace=1.0):
    return {
        "name": "tiny", "chips": 1,
        "config": {"name": "tiny", "kind": "dense", "model": dict(TINY),
                   "draft": {"rule": "draft_variant", "scale": 2}},
        "traffic": {"prompt_buckets": [8, 16], "prompt_weights": [0.5, 0.5],
                    "out_median": 6, "out_sigma": 0.5, "out_min": 3,
                    "out_max": 10, "rate_rps": 2.0, "burst": slots,
                    "n_requests": 200},
        "cell": {"method": {"name": method, "K": 8, "ell": 100,
                            "alpha": 5e-4, "eta": 1e-3},
                 "engine": {"L_max": 4, "bit_budget": 5000.0,
                            "temperature": 0.2, "wire_codec": "v2"},
                 "channel": {"uplink_bps": 1e6, "downlink_bps": 2e7},
                 "slots": slots,
                 "audit": {"rounds": 3, "min_rounds": 2},
                 "trace_seconds": seconds_trace,
                 "limits": {"target_gap": 0.06, "draft_gap": 0.06,
                            "target_rest": 0.004, "draft_rest": 0.004}},
        "end_to_end": [{"name": n, "unit": u} for n, u in
                       (("out_tok_s", "tokens/s"), ("setup_s", "s"))],
        "per_layer": [],
    }


@pytest.fixture(scope="session")
def cpu_devs():
    import jax
    return jax.devices()
