"""The harness's own arithmetic, on the CPU in seconds: trace reduction
on a synthetic trace with known intervals, FLOP and byte counts against
hand-computed values for both configurations, the metric readers on
recorded deliveries, and the traffic generator."""
import json
import os
import types

import numpy as np
import pytest

from conftest import BENCH
from harness import cell, check, counts, traffic, tracefile


def _cfg(name):
    from repro.configs.base import ModelConfig
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return ModelConfig(**json.load(f)["model"])


# -- trace reduction -----------------------------------------------------
def test_reduce_synthetic_trace():
    D, H = "/device:TPU:0", "/host:CPU"
    ev = [
        (D, "XLA Ops", "fusion.1", 0, 100),
        (D, "XLA Ops", "fusion.2", 50, 100),      # overlaps: union 0-150
        (D, "XLA Ops", "fusion.1", 300, 100),     # gap 150-300
        (D, "XLA Ops", "copy.3", 450, 50),        # gap 400-450
        (D, "XLA Modules", "jit__draft_round(12)", 0, 150),
        (D, "XLA Modules", "jit__draft_round(12)", 300, 100),
        (D, "XLA Modules", "jit__verify_round(7)", 450, 50),
        (H, "python", "bench.verify", 140, 200),  # covers gap midpoint 225
        (H, "python", "bench.draft", 100, 400),   # wider: not innermost
    ]
    r = tracefile.reduce(ev, (10.0, 10.5))
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["window_s"] == pytest.approx(0.5)
    assert r["programs"]["jit__draft_round"] == {"n": 2, "dev_s": 250e-9}
    assert r["programs"]["jit__verify_round"]["n"] == 1
    assert r["device_ops"][0] == ["jit__draft_round/fusion.1", 200e-9]
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    assert gaps["bench.verify"] == pytest.approx(150e-9)   # 150-300
    assert gaps["bench.draft"] == pytest.approx(50e-9)     # 400-450


def test_reduce_two_devices_averages_busy():
    ev = [("/device:TPU:0", "XLA Ops", "a", 0, 100),
          ("/device:TPU:1", "XLA Ops", "a", 0, 300)]
    assert tracefile.reduce(ev, (0, 1))["busy_s"] == pytest.approx(200e-9)


def test_module_name():
    assert tracefile.module_name("jit__verify_round(3)") == "jit__verify_round"
    assert tracefile.module_name("jit__draft_round") == "jit__draft_round"


# -- counts ----------------------------------------------------------------
def test_counts_qwen_by_hand():
    c = _cfg("qwen2.5-3b")
    # 2048*16*128*2 + 2048*2*128*2 + 3*2048*11008
    assert counts.layer_matmul_params(c) == 77_070_336
    assert counts.matmul_params_per_token(c) == 77_070_336 * 36 + 2048 * 151936
    # layers + norms + QKV bias, final norm, tied head/embedding, bf16
    assert counts.weight_bytes(c) == 2 * ((77_070_336 + 4096 + 2560) * 36
                                          + 2048 + 2048 * 151936)
    assert counts.kv_bytes_per_token(c) == 36 * 2 * 2 * 128 * 2
    fl, by = counts.verify_call(c, [100], 8)
    attn = 36 * 4 * 16 * 128 * sum(100 + i + 1 for i in range(9))
    assert fl == 2 * counts.matmul_params_per_token(c) * 9 + attn
    assert by == (counts.weight_bytes(c) + 100 * 36864 + 9 * 36864
                  + 9 * 151936 * 4)


def test_counts_deepseek_by_hand():
    c = _cfg("deepseek-7b-l12")
    assert counts.layer_matmul_params(c) == 202_375_168
    assert counts.kv_bytes_per_token(c) == 196_608
    # untied: the head is read, the embedding table is only gathered
    assert counts.weight_bytes(c) == 2 * ((202_375_168 + 8192) * 12 + 4096
                                          + 4096 * 102400)
    fl, by = counts.prefill_call(c, 3)
    n = 2
    assert fl == (2 * 12 * 202_375_168 * n + 2 * 4096 * 102400
                  + 12 * 4 * 32 * 128 * (1 + 2))
    assert by == counts.weight_bytes(c) + n * 196_608
    fl, by = counts.draft_call(c, [10, 20], 1)
    assert by == (2 * counts.weight_bytes(c)
                  + (10 + 11 + 20 + 21) * 196_608 + 2 * 2 * 196_608
                  + 2 * 2 * 102400 * 4 * 2)


def test_least_time_and_peaks():
    p = counts.load_peaks("TPU v5 lite")
    t, bound = counts.least_time(197e12, 819e9 / 2, p)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    with pytest.raises(KeyError):
        counts.load_peaks("cpu")


# -- metric readers on recorded deliveries ---------------------------------
def _rec():
    r = types.SimpleNamespace()
    r.t_open, r.t_close, r.window_s = 10.0, 12.0, 2.0
    r.now_open, r.now_close = 1.0, 3.0
    # (rid, serving now, wall, tokens, finished)
    r.deliveries = [(0, 0.5, 9.0, 1, False), (0, 1.5, 10.5, 2, False),
                    (1, 1.6, 10.6, 1, False), (0, 2.0, 11.0, 1, False),
                    (1, 2.6, 11.9, 3, True), (0, 4.0, 12.5, 1, True)]
    r.in_window = types.MethodType(cell.Records.in_window, r)
    r.gaps = types.MethodType(cell.Records.gaps, r)
    r.link_open, r.link_close = (1000.0, 0.5), (8000.0, 1.5)
    r.calls = [("draft", 10.1, 10.2, 0.05, (0,), (10,)),
               ("verify", 10.2, 10.3, 0.02, (0, 1), (10, 12)),
               ("verify", 10.4, 10.5, 0.04, (1,), (13,))]
    return r


@pytest.mark.parametrize("name,want", [
    ("out_tok_s", 7 / 2.0),
    ("served_itl_p95_ms", np.percentile([1000.0, 500.0, 1000.0], 95)),
    ("served_tpot_ms", (1.0 + 0.5 + 1.0) / (2 + 1 + 3) * 1e3),
    ("itl_p95_ms", np.percentile([1500.0, 500.0, 1300.0], 95)),
    ("uplink_bits_per_tok", 7000.0 / 7),
    ("slots_per_verify", 1.5),
    ("tok_per_slot_round", 7 / 4),
    ("draft_step_ms", 50.0),
    ("verify_step_ms", 30.0),
    ("offclock_share", (1 - 0.11 / 2.0) * 100),
])
def test_metric_readers(name, want):
    assert cell.reader(name)(_rec()) == pytest.approx(want)


def test_readers_find_nothing_to_read():
    r = _rec()
    r.deliveries, r.calls, r.trace = [], [], None
    for name in ("out_tok_s", "itl_p95_ms", "served_itl_p95_ms",
                 "served_tpot_ms", "slots_per_verify",
                 "draft_step_ms", "idle_share"):
        assert cell.reader(name)(r) is None


# -- traffic ----------------------------------------------------------------
T = {"prompt_buckets": [128, 256, 512, 1024],
     "prompt_weights": [0.4, 0.3, 0.2, 0.1], "out_median": 32,
     "out_sigma": 0.6, "out_min": 8, "out_max": 96, "rate_rps": 0.5,
     "burst": 4}


def test_trace_deterministic_per_seed():
    a = traffic.make_trace(T, 1000, 2**31 + 12345, 50)
    b = traffic.make_trace(T, 1000, 2**31 + 12345, 50)
    c = traffic.make_trace(T, 1000, 7, 50)
    key = lambda tr: [(x.t_arrival, x.max_new_tokens, x.seed,
                       x.prompt.tolist()) for x in tr]
    assert key(a) == key(b) and key(a) != key(c)


def test_every_seed_draws_the_same_work():
    a = traffic.make_trace(T, 1000, 1, 200)
    b = traffic.make_trace(T, 1000, 2, 200)
    for f in (lambda x: len(x.prompt), lambda x: x.max_new_tokens):
        assert sorted(map(f, a)) == sorted(map(f, b))
    gaps = [np.sort(np.diff([x.t_arrival for x in tr])) for tr in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], atol=1e-9)


def test_the_burst_is_the_same_work_for_every_seed():
    a, b = (traffic.make_trace(T, 1000, s, 400) for s in (5, 6))
    assert sorted(len(x.prompt) for x in a[:4]) == [128, 128, 256, 512]
    assert sorted(x.max_new_tokens for x in a[:4]) == \
        sorted(x.max_new_tokens for x in b[:4]) == [16, 26, 39, 64]


def test_the_head_is_the_same_work_in_the_same_order():
    t = dict(T, fixed_head=12)
    a, b = (traffic.make_trace(t, 1000, s, 400) for s in (5, 2**31 + 6))
    work = lambda tr: [(x.t_arrival, len(x.prompt), x.max_new_tokens)
                       for x in tr]
    assert work(a[:12]) == work(b[:12]) and work(a) != work(b)
    assert a[0].prompt.tolist() != b[0].prompt.tolist()
    assert [x.t_arrival for x in a[:5]] == [0.0] * 4 + [a[4].t_arrival] \
        and a[4].t_arrival > 0


def test_bucket_and_length_frequencies():
    tr = traffic.make_trace(T, 1000, 3, 1000)
    lens = [len(x.prompt) for x in tr]
    assert [lens.count(b) for b in T["prompt_buckets"]] == [400, 300, 200, 100]
    outs = np.array([x.max_new_tokens for x in tr])
    assert outs.min() == 8 and outs.max() <= 96
    assert np.median(outs) == pytest.approx(32, abs=1)
    t = np.array([x.t_arrival for x in tr])
    assert (t[:4] == 0).all() and np.all(np.diff(t) >= 0)
    assert (len(t) - 4) / t[-1] == pytest.approx(0.5, rel=0.1)


# -- the comparison's arithmetic ---------------------------------------------
def test_logit_gap_and_its_rest_outside_the_head():
    rng = np.random.default_rng(0)
    V, d, T = 600, 24, 0.2
    w = {"embed": rng.standard_normal((V, d)).astype(np.float32) / d ** 0.5}
    space = check.HeadSpace(w)
    z = rng.standard_normal((3, d)) @ w["embed"].T.astype(np.float64)
    # an error of the hidden state: a gap, and nothing outside the head
    dz = rng.standard_normal((3, d)) @ w["embed"].T.astype(np.float64) * 0.01
    gap, rest, tv = check.row_readings(check._softmax_t(z + dz + 3.0, T), z,
                                       T, space)
    want = np.sqrt(((dz - dz.mean(-1, keepdims=True)) ** 2).mean(-1))
    np.testing.assert_allclose(gap, want, rtol=1e-6)
    assert (rest < 1e-5).all() and (tv > 0).all()
    # noise on every logit: most of it lies outside a 24-wide head
    n = rng.standard_normal((3, V)) * 0.01
    gap, rest, _ = check.row_readings(check._softmax_t(z + n, T), z, T,
                                      space)
    np.testing.assert_allclose(rest / gap, np.sqrt((V - d - 1) / V),
                               rtol=0.05)
