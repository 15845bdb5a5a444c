"""Serving-layer load study: batching policies, KV layouts, schedules.

Three studies over the SAME seeded Poisson arrival traces, on the same
deterministic discrete-event clock (calibrated fixed per-round compute
costs — host timing noise must not decide a scheduler comparison):

  policy    continuous vs static batching across arrival rates:
            continuous refills engine slots the moment a request
            completes; static drains the whole batch first and pays for
            the idle slots at high load.

  paged     paged KV pool vs dense per-slot caches under the SAME KV
            memory budget (dense_slots x cache_len positions per layer).
            Dense caches reserve the worst case for every slot, so the
            budget backs only ``dense_slots`` concurrent requests; the
            page pool holds each request's ACTUAL length, so the same
            bytes admit more slots (preemption backstops the
            oversubscription).  Headline: strictly more peak
            concurrency, throughput no worse.

  pipeline  lockstep barrier rounds vs the event-driven pipelined loop
            (serve/events.py) at the paper's default 1 Mbit/s uplink:
            same packed wire payloads, same token streams bit for bit —
            but edge drafting, uplink serialisation, cloud verify and
            downlink overlap across requests (plus optimistic draft-
            ahead), so mean end-to-end request latency must drop.

  wire      wire codec v1 (fixed-width) vs v2 (entropy-coded,
            core/coding.py): bits/round on the SAME token streams (the
            codec moves bytes, never tokens), the coded size against
            the core/bits entropy reference (eq. (1) + draft ids + raw
            β side info), end-to-end latency across uplink bandwidths,
            and the calibrated online coded-size budget model's fit.

  cells     multi-cell topology (serve/cells.py) in the DOWNLINK-
            LIMITED regime (broadcast <= 1 Mbit/s): the same workload
            served through {1, 2, 4} radio cells — per-cell uplinks and
            broadcast downlinks, one cloud verifier — with verdict
            batching off vs on.  Token streams must be identical to the
            single-cell reference everywhere; batching (one coded
            frame per cell per round instead of one framed message per
            verdict) must strictly cut downlink bits/round.

  transport real two-process sockets (serve/net.py) vs the simulator
            as differential oracle: the SAME seeded trace through a
            threaded CloudServer must emit token streams bit-identical
            to the modeled run in both pipeline modes, with MEASURED
            wall-clock RPC/verify/draft latency reported next to the
            simulator's modeled clock.

Results go to experiments/bench/serve_load.csv and the perf-trajectory
JSONs CI tracks: experiments/bench/BENCH_serve.json (throughput, p50/p95
latency, peak pages, preemptions), experiments/bench/BENCH_pipeline.json
(lockstep-vs-pipelined latency, spec hit rate), experiments/bench/
BENCH_wire.json (v1-vs-v2 bits/round and latency, reference ratio),
experiments/bench/BENCH_cells.json (per-topology downlink bits/round,
batching ratio, makespans) and experiments/bench/BENCH_transport.json
(measured vs modeled round latency, stream equality).

    PYTHONPATH=src python -m benchmarks.serve_load --smoke
    PYTHONPATH=src python -m benchmarks.serve_load            # trained pair
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os

import jax
import numpy as np

from repro import configs
from repro.core import EdgeCloudEngine, EngineConfig, MethodConfig
from repro.core import bits as rbits
from repro.core.channel import ChannelConfig
from repro.core.pages import pages_for
from repro.models import init_params
from repro.obs import DecompTracker, Obs
from repro.serve import (ServeConfig, ServeSession, TraceConfig,
                         poisson_trace)

from benchmarks import common

KEYS = ["policy", "rate_rps", "throughput_tok_s", "latency_p50_s",
        "latency_p99_s", "queue_wait_mean_s", "uplink_wait_mean_s",
        "uplink_utilization", "rejection_rate", "n_finished", "makespan_s"]

PAGE_SIZE = 8


def _smoke_pair(arch="qwen2.5-3b", seed=0):
    tc = configs.smoke_variant(configs.get_config(arch))
    dc = configs.draft_variant(tc, 2)
    tp = init_params(tc, jax.random.PRNGKey(seed + 1))
    dp = init_params(dc, jax.random.PRNGKey(seed + 2))
    return dc, dp, tc, tp


def _calibrate(dc, dp, tc, tp, method, ecfg, channel, max_batch,
               prompt_len):
    """Median warm-round compute costs -> one shared event clock."""
    cal = EdgeCloudEngine(dc, dp, tc, tp, method, ecfg, channel, seed=0)
    cal_prompts = np.zeros((max_batch, prompt_len), np.int32) + 7
    cal_rounds, _ = cal.run(cal_prompts, 5)
    t_slm = float(np.median([r["t_slm"] for r in cal_rounds[2:]]))
    t_llm = float(np.median([r["t_llm"] for r in cal_rounds[2:]]))
    return t_slm, t_llm


def policy_study(pair, rates, n_requests, max_batch, prompt_len, min_new,
                 max_new, method, ecfg, channel, t_slm, t_llm, cache_len):
    dc, dp, tc, tp = pair
    rows = []
    for rate in rates:
        trace_cfg = TraceConfig(
            n_requests=n_requests, rate_rps=rate, prompt_len=prompt_len,
            min_new_tokens=min_new, max_new_tokens=max_new,
            vocab=tc.vocab, seed=7)
        for policy in ("continuous", "static"):
            eng = EdgeCloudEngine(dc, dp, tc, tp, method, ecfg,
                                  channel, seed=0)
            sess = ServeSession(eng, ServeConfig(
                max_batch=max_batch, policy=policy, cache_len=cache_len,
                t_slm_s=t_slm, t_llm_s=t_llm))
            rep = sess.run_trace(poisson_trace(trace_cfg))
            rows.append({"rate_rps": rate,
                         **{k: rep.summary()[k] for k in KEYS
                            if k != "rate_rps"}})
    return rows


def paged_study(pair, n_requests, dense_slots, paged_slots, prompt_len,
                min_new, max_new, rate, method, ecfg, channel, t_slm,
                t_llm):
    """Paged vs contiguous at a FIXED per-layer KV memory budget of
    dense_slots x cache_len positions."""
    dc, dp, tc, tp = pair
    cache_len = pages_for(prompt_len + max_new + ecfg.L_max + 1,
                          PAGE_SIZE) * PAGE_SIZE
    budget_tokens = dense_slots * cache_len
    n_pages = budget_tokens // PAGE_SIZE
    trace_cfg = TraceConfig(
        n_requests=n_requests, rate_rps=rate, prompt_len=prompt_len,
        min_new_tokens=min_new, max_new_tokens=max_new, vocab=tc.vocab,
        seed=11)
    out = {"memory_budget_tokens": budget_tokens, "page_size": PAGE_SIZE,
           "cache_len": cache_len}
    for layout, slots, ps in (("contiguous", dense_slots, 0),
                              ("paged", paged_slots, PAGE_SIZE)):
        eng = EdgeCloudEngine(dc, dp, tc, tp, method, ecfg, channel,
                              seed=0)
        sess = ServeSession(eng, ServeConfig(
            max_batch=slots, cache_len=cache_len, page_size=ps,
            n_pages=n_pages if ps else None,
            t_slm_s=t_slm, t_llm_s=t_llm))
        rep = sess.run_trace(poisson_trace(trace_cfg))
        out[layout] = {
            "max_batch": slots,
            "throughput_tok_s": rep.throughput_tok_s,
            "latency_p50_s": rep.latency_p50_s,
            "latency_p95_s": rep.latency_p95_s,
            "peak_active": rep.peak_active,
            "peak_kv_tokens": (rep.peak_pages_in_use * PAGE_SIZE
                               if ps else rep.peak_active * cache_len),
            "peak_pages_in_use": rep.peak_pages_in_use,
            "n_preempted": rep.n_preempted,
            "n_finished": rep.n_finished,
            "n_rejected": rep.n_rejected,
            "makespan_s": rep.makespan_s,
        }
    pg, ct = out["paged"], out["contiguous"]
    out["verdict"] = {
        "more_concurrency": pg["peak_active"] > ct["peak_active"],
        "throughput_ratio": pg["throughput_tok_s"]
        / max(ct["throughput_tok_s"], 1e-9),
        "peak_kv_ratio": pg["peak_kv_tokens"] / max(budget_tokens, 1),
        "ok": (pg["peak_active"] > ct["peak_active"]
               and pg["throughput_tok_s"]
               >= 0.99 * ct["throughput_tok_s"])
        or (pg["throughput_tok_s"] >= ct["throughput_tok_s"]
            and pg["peak_kv_tokens"] < budget_tokens),
    }
    return out


def pipeline_study(pair, n_requests, max_batch, prompt_len, min_new,
                   max_new, rate, method, ecfg, t_slm, t_llm, cache_len):
    """Lockstep vs event-driven pipelined serving on the SAME trace with
    the SAME calibrated compute costs, over the paper's default 1 Mbit/s
    uplink (ChannelConfig defaults).  Token streams must be identical;
    mean end-to-end latency must be strictly lower pipelined.  Both legs
    run with the observability layer live (obs never perturbs tokens —
    the streams_identical gate would catch it): the JSON carries each
    leg's metrics counters and, on the lockstep leg, the Theorem-1
    rejection decomposition + conformal coverage snapshot."""
    dc, dp, tc, tp = pair
    channel = ChannelConfig()          # 1 Mbit/s up, the paper's regime
    trace_cfg = TraceConfig(
        n_requests=n_requests, rate_rps=rate, prompt_len=prompt_len,
        min_new_tokens=min_new, max_new_tokens=max_new, vocab=tc.vocab,
        seed=13)
    out = {"uplink_bps": channel.uplink_bps, "rate_rps": rate,
           "n_requests": n_requests, "max_batch": max_batch}
    streams = {}
    for pipeline in ("lockstep", "pipelined"):
        obs = Obs.on(decomp=DecompTracker(method.alpha, method.eta,
                                          method.ell)
                     if pipeline == "lockstep" else None)
        eng = EdgeCloudEngine(
            dc, dp, tc, tp, method,
            dataclasses.replace(ecfg,
                                collect_theory=obs.decomp is not None),
            channel, seed=0)
        sess = ServeSession(eng, ServeConfig(
            max_batch=max_batch, cache_len=cache_len, pipeline=pipeline,
            t_slm_s=t_slm, t_llm_s=t_llm), obs=obs)
        rep = sess.run_trace(poisson_trace(trace_cfg))
        streams[pipeline] = {r.rid: tuple(r.tokens) for r in rep.requests}
        out[pipeline] = {
            "latency_mean_s": rep.latency_mean_s,
            "latency_p50_s": rep.latency_p50_s,
            "latency_p95_s": rep.latency_p95_s,
            "ttft_mean_s": rep.ttft_mean_s,
            "uplink_wait_mean_s": rep.uplink_wait_mean_s,
            "uplink_utilization": rep.uplink_utilization,
            "throughput_tok_s": rep.throughput_tok_s,
            "makespan_s": rep.makespan_s,
            "n_rounds": rep.n_rounds,
            "n_spec_hits": rep.n_spec_hits,
            "n_spec_misses": rep.n_spec_misses,
            "n_finished": rep.n_finished,
            "obs": {"trace_events": obs.tracer.n_events,
                    "counters": obs.metrics.snapshot()["counters"]},
        }
        if obs.decomp is not None:
            rec_ok, rec_err = obs.decomp.reconcile()
            out[pipeline]["obs"]["decomp"] = {
                "reconcile_ok": bool(rec_ok),
                "reconcile_max_err": float(rec_err),
                "coverage": obs.decomp.coverage(),
            }
    lk, pp = out["lockstep"], out["pipelined"]
    out["verdict"] = {
        "streams_identical": streams["lockstep"] == streams["pipelined"],
        "latency_ratio": pp["latency_mean_s"]
        / max(lk["latency_mean_s"], 1e-12),
        "makespan_ratio": pp["makespan_s"] / max(lk["makespan_s"], 1e-12),
        "ok": (streams["lockstep"] == streams["pipelined"]
               and pp["latency_mean_s"] < lk["latency_mean_s"]),
    }
    return out


def wire_study(pair, n_rounds, batch, prompt_len, n_requests, max_batch,
               min_new, max_new, rate, method, ecfg, t_slm, t_llm,
               cache_len, uplinks=(2.5e5, 1e6, 4e6), smoke=True):
    """Wire codec v1 (fixed-width) vs v2 (entropy-coded) on identical
    token streams: mean uplink bits/round against the core/bits
    entropy reference, per-payload dominance (v2 must never ship more
    bytes than v1), pipelined end-to-end latency across uplink
    bandwidths, and the calibrated budget model's fit."""
    dc, dp, tc, tp = pair
    V, L_max = tc.vocab, ecfg.L_max

    def eng(codec, budget="analytic", channel=None, theory=False):
        return EdgeCloudEngine(
            dc, dp, tc, tp, method,
            dataclasses.replace(ecfg, wire_codec=codec,
                                budget_model=budget,
                                collect_theory=theory),
            channel or ChannelConfig(), seed=0)

    prompts = np.full((batch, prompt_len), 7, np.int32)
    out = {"V": V, "ell": method.ell, "L_max": L_max,
           "n_rounds": n_rounds, "batch": batch}
    rounds_by, streams_by = {}, {}
    for codec in ("v1", "v2"):
        # collect_theory keeps per-position K so the reference is the
        # ONE formula tests pin (bits.draft_message_reference_bits)
        rounds, toks = eng(codec, theory=True).run(prompts, n_rounds)
        rounds_by[codec] = rounds
        streams_by[codec] = [tuple(t) for t in toks]
        up = [float(r["wire_bits_row"][r["active"]].mean())
              for r in rounds]
        down = [float(r["verdict_bits_row"][r["active"]].mean())
                for r in rounds]
        ref = [float(np.mean([
            rbits.draft_message_reference_bits(
                V, method.ell, r["K_seq"][b, :int(r["L_live"][b])],
                L_max, adaptive=method.name == "csqs")
            for b in np.nonzero(r["active"])[0]])) for r in rounds]
        out[codec] = {
            "uplink_bits_per_round": float(np.mean(up)),
            "downlink_bits_per_round": float(np.mean(down)),
            "reference_bits_per_round": float(np.mean(ref)),
        }
    # hard invariant (the fallback flag's worst case): v2 is never more
    # than one BYTE over v1.  Strict byte dominance additionally holds
    # in the small-vocabulary smoke regime, where the coded body always
    # wins by more than the flag bit — at real vocab sizes a degenerate
    # 1-draft payload can legally land one byte over.
    per_payload_flag_ok = all(
        (r2["wire_bits_row"] <= r1["wire_bits_row"] + 8).all()
        for r1, r2 in zip(rounds_by["v1"], rounds_by["v2"]))
    per_payload_dominates = all(
        (r2["wire_bits_row"] <= r1["wire_bits_row"]).all()
        for r1, r2 in zip(rounds_by["v1"], rounds_by["v2"]))
    per_payload_ok = per_payload_flag_ok and \
        (per_payload_dominates or not smoke)
    # latency across bandwidths on the SAME trace, pipelined schedule
    trace_cfg = TraceConfig(
        n_requests=n_requests, rate_rps=rate, prompt_len=prompt_len,
        min_new_tokens=min_new, max_new_tokens=max_new, vocab=V, seed=17)
    bw_rows, bw_streams_ok = [], True
    for bps in uplinks:
        row = {"uplink_bps": bps}
        tstreams = {}
        for codec in ("v1", "v2"):
            sess = ServeSession(
                eng(codec, channel=ChannelConfig(uplink_bps=bps)),
                ServeConfig(max_batch=max_batch, cache_len=cache_len,
                            pipeline="pipelined", t_slm_s=t_slm,
                            t_llm_s=t_llm))
            rep = sess.run_trace(poisson_trace(trace_cfg))
            tstreams[codec] = {r.rid: tuple(r.tokens)
                               for r in rep.requests}
            row[codec] = {
                "latency_mean_s": rep.latency_mean_s,
                "latency_p95_s": rep.latency_p95_s,
                "uplink_utilization": rep.uplink_utilization,
                "throughput_tok_s": rep.throughput_tok_s,
            }
        row["latency_ratio"] = row["v2"]["latency_mean_s"] \
            / max(row["v1"]["latency_mean_s"], 1e-12)
        bw_streams_ok &= tstreams["v1"] == tstreams["v2"]
        bw_rows.append(row)
    out["bandwidth_study"] = bw_rows
    # calibrated budget model: with v2 + calibration the edge's L^t
    # estimate must track the coded bytes better than the analytic
    # formula tracks them (mean |obs − est| per payload)
    cal = eng("v2", budget="calibrated")
    cal.init_slots(1, cache_len)
    cal.admit_slot(0, np.full((prompt_len,), 7, np.int32), 7)
    err_ana, err_cal = [], []
    for _ in range(n_rounds):
        # the scale L^t ACTUALLY budgeted with this round — read before
        # the round folds its own observation into the EMA
        scale = float(cal.edge.coded_scale[0])
        m = cal.run_round()
        obs = float(m["wire_bits_row"][0])
        est = float(m["bits_row"][0])
        err_ana.append(abs(obs - est))
        err_cal.append(abs(obs - est * scale))
    out["budget_study"] = {
        "analytic_abs_err_bits": float(np.mean(err_ana[1:])),
        "calibrated_abs_err_bits": float(np.mean(err_cal[1:])),
        "final_scale": float(cal.edge.coded_scale[0]),
    }
    v1b = out["v1"]["uplink_bits_per_round"]
    v2b = out["v2"]["uplink_bits_per_round"]
    ref = out["v2"]["reference_bits_per_round"]
    # the verdict's latency leg: the bandwidth nearest the paper's
    # 1 Mbit/s regime (exact when the default uplinks list is used)
    mbit = min(bw_rows, key=lambda r: abs(r["uplink_bps"] - 1e6))
    out["verdict"] = {
        "streams_identical": (streams_by["v1"] == streams_by["v2"]
                              and bw_streams_ok),
        "per_payload_v2_not_longer": bool(per_payload_dominates),
        "per_payload_within_flag_byte": bool(per_payload_flag_ok),
        "bits_ratio_v2_v1": v2b / max(v1b, 1e-9),
        "ratio_to_reference": v2b / max(ref, 1e-9),
        "latency_ratio_1mbit": mbit["latency_ratio"],
        "ok": (streams_by["v1"] == streams_by["v2"] and bw_streams_ok
               and per_payload_ok and v2b < v1b
               and v2b <= 1.15 * ref
               and mbit["latency_ratio"] <= 1.0),
    }
    return out


def cell_study(pair, n_requests, prompt_len, min_new, max_new, rate,
               method, ecfg, t_slm, t_llm, cache_len,
               cell_grid=(1, 2, 4), downlink_bps=5e5):
    """Multi-cell serving in the downlink-limited regime: the broadcast
    carries one framed message per verdict (off) or one coded frame per
    cell per round (on).  Slots are provisioned at 2 per cell for the
    LARGEST topology so every cell has concurrency to coalesce — the
    regime where batching matters — and the total slot count is fixed
    across topologies, so every run shares one engine shape AND one
    token-stream reference."""
    dc, dp, tc, tp = pair
    max_batch = 2 * max(cell_grid)
    channel = ChannelConfig(downlink_bps=downlink_bps)
    trace_cfg = TraceConfig(
        n_requests=n_requests, rate_rps=rate, prompt_len=prompt_len,
        min_new_tokens=min_new, max_new_tokens=max_new, vocab=tc.vocab,
        seed=19, cells=max(cell_grid))
    out = {"downlink_bps": downlink_bps,
           "uplink_bps": channel.uplink_bps, "rate_rps": rate,
           "n_requests": n_requests, "max_batch": max_batch,
           "cell_grid": list(cell_grid), "topologies": []}
    streams = {}
    for n_cells in cell_grid:
        row = {"n_cells": n_cells}
        for batch in (False, True):
            for pipeline in ("lockstep", "pipelined"):
                eng = EdgeCloudEngine(dc, dp, tc, tp, method, ecfg,
                                      channel, seed=0)
                sess = ServeSession(eng, ServeConfig(
                    max_batch=max_batch, cache_len=cache_len,
                    pipeline=pipeline, n_cells=n_cells,
                    verdict_batch=batch, t_slm_s=t_slm, t_llm_s=t_llm))
                rep = sess.run_trace(poisson_trace(trace_cfg))
                streams[(n_cells, batch, pipeline)] = {
                    r.rid: tuple(r.tokens) for r in rep.requests}
                key = ("batched" if batch else "per_verdict") \
                    + "_" + pipeline
                row[key] = {
                    "makespan_s": rep.makespan_s,
                    "latency_mean_s": rep.latency_mean_s,
                    "n_rounds": rep.n_rounds,
                    "downlink_bits_total": rep.downlink_bits_total,
                    "downlink_msgs": rep.downlink_msgs,
                    "downlink_bits_per_round": rep.downlink_bits_total
                    / max(rep.n_rounds, 1),
                    "downlink_utilization": rep.downlink_utilization,
                    "uplink_utilization": rep.uplink_utilization,
                    "uplink_wait_mean_s": rep.uplink_wait_mean_s,
                    "n_finished": rep.n_finished,
                }
        # the gate compares LOCKSTEP bits/round: rounds are well-defined
        # barriers there, and identical streams pin the round count
        pv, bt = row["per_verdict_lockstep"], row["batched_lockstep"]
        row["verdict"] = {
            "downlink_bits_ratio": bt["downlink_bits_per_round"]
            / max(pv["downlink_bits_per_round"], 1e-9),
            "batching_reduces_bits": bt["downlink_bits_per_round"]
            < pv["downlink_bits_per_round"],
            "batching_reduces_msgs": bt["downlink_msgs"]
            < pv["downlink_msgs"],
        }
        out["topologies"].append(row)
    ref = streams[(cell_grid[0], False, "lockstep")]
    out["verdict"] = {
        "streams_identical": all(s == ref for s in streams.values()),
        "bits_ratios": [r["verdict"]["downlink_bits_ratio"]
                        for r in out["topologies"]],
        "ok": (all(s == ref for s in streams.values())
               and all(r["verdict"]["batching_reduces_bits"]
                       and r["verdict"]["batching_reduces_msgs"]
                       for r in out["topologies"])),
    }
    return out


def transport_study(n_requests, prompt_len, min_new, max_new, rate,
                    method, ecfg, t_slm, t_llm, cache_len, n_cells=2,
                    max_batch=4, arch="qwen2.5-3b", seed=0):
    """Real sockets vs the discrete-event simulator as differential
    oracle: the SAME seeded trace through an in-process threaded
    ``CloudServer`` (one TCP connection per cell) must yield token
    streams bit-identical to the simulator in BOTH pipeline modes —
    the transport moves bytes and clocks, never tokens.  The tcp side
    reports MEASURED wall-clock (VERIFY→VERDICTS round trips, the
    server's verify time, edge draft time, makespan) next to the sim's
    modeled clock.  Always runs the random-init smoke pair: the
    handshake rebuilds models from (arch, seed) — parameters never
    cross the wire — so a trained checkpoint pair has no two-process
    equivalent."""
    from repro.serve import CloudServer, EdgeClient

    dc, dp, tc, tp = _smoke_pair(arch, seed)
    trace_cfg = TraceConfig(
        n_requests=n_requests, rate_rps=rate, prompt_len=prompt_len,
        min_new_tokens=min_new, max_new_tokens=max_new, vocab=tc.vocab,
        seed=23, cells=n_cells)
    out = {"n_cells": n_cells, "max_batch": max_batch,
           "n_requests": n_requests, "arch": arch, "modes": {}}
    server = CloudServer().start()
    ok = True
    try:
        for pipeline in ("lockstep", "pipelined"):
            # lockstep also exercises the coalesced verdict frames
            cfg_kw = dict(max_batch=max_batch, cache_len=cache_len,
                          pipeline=pipeline, n_cells=n_cells,
                          verdict_batch=(pipeline == "lockstep"))
            eng = EdgeCloudEngine(dc, dp, tc, tp, method, ecfg,
                                  ChannelConfig(), seed=seed)
            sim = ServeSession(eng, ServeConfig(
                t_slm_s=t_slm, t_llm_s=t_llm, **cfg_kw)).run_trace(
                poisson_trace(trace_cfg))
            sim_streams = {r.rid: tuple(r.tokens) for r in sim.requests}
            client = EdgeClient(dc, dp, method, ecfg,
                                ServeConfig(**cfg_kw), arch=arch,
                                smoke=True, host=server.host,
                                port=server.port, seed=seed,
                                session_id=f"bench-{pipeline}")
            with client:
                rep = client.run_trace(poisson_trace(trace_cfg))
            identical = rep.streams() == sim_streams
            ok &= identical
            out["modes"][pipeline] = {
                "streams_identical": identical,
                "sim_modeled": {
                    "makespan_s": sim.makespan_s,
                    "latency_mean_s": sim.latency_mean_s,
                    "n_rounds": sim.n_rounds,
                },
                "tcp_measured": {
                    "makespan_s": rep.makespan_s,
                    "n_verify_rpcs": rep.n_verify_rpcs,
                    "rpc_round_s": rep.rpc_round_s,
                    "t_llm_s": rep.t_llm_s,
                    "t_slm_s": rep.t_slm_s,
                    "n_finished": rep.n_finished,
                    "n_spec_hits": rep.n_spec_hits,
                },
            }
    finally:
        server.stop()
    out["verdict"] = {"streams_identical": ok, "ok": ok}
    return out


def run(smoke: bool = False):
    if smoke:
        pair = _smoke_pair()
        rates = [1.0, 4.0, 16.0]
        n_requests, max_batch = 12, 3
        prompt_len, min_new, max_new = 10, 6, 16
        paged_args = dict(n_requests=10, dense_slots=2, paged_slots=4,
                          prompt_len=10, min_new=4, max_new=24, rate=16.0)
    else:
        dc, dp, tc, tp, _ = common.trained_pair()
        pair = (dc, dp, tc, tp)
        rates = [0.5, 2.0, 8.0, 32.0]
        n_requests, max_batch = 32, 4
        prompt_len, min_new, max_new = 12, 8, 32
        paged_args = dict(n_requests=24, dense_slots=3, paged_slots=6,
                          prompt_len=12, min_new=6, max_new=32, rate=32.0)
    method = MethodConfig("csqs")
    ecfg = EngineConfig(L_max=4)
    channel = ChannelConfig(uplink_bps=common.BENCH_UPLINK_BPS)
    cache_len = prompt_len + max_new + ecfg.L_max + 8

    t_slm, t_llm = _calibrate(*pair, method, ecfg, channel, max_batch,
                              prompt_len)
    rows = policy_study(pair, rates, n_requests, max_batch, prompt_len,
                        min_new, max_new, method, ecfg, channel, t_slm,
                        t_llm, cache_len)
    paged = paged_study(pair, method=method, ecfg=ecfg, channel=channel,
                        t_slm=t_slm, t_llm=t_llm, **paged_args)
    pipe = pipeline_study(pair, n_requests=n_requests,
                          max_batch=max_batch, prompt_len=prompt_len,
                          min_new=min_new, max_new=max_new,
                          rate=max(rates), method=method, ecfg=ecfg,
                          t_slm=t_slm, t_llm=t_llm, cache_len=cache_len)
    wire = wire_study(pair, n_rounds=8 if smoke else 12, batch=max_batch,
                      prompt_len=prompt_len, n_requests=n_requests,
                      max_batch=max_batch, min_new=min_new,
                      max_new=max_new, rate=max(rates), method=method,
                      ecfg=ecfg, t_slm=t_slm, t_llm=t_llm,
                      cache_len=cache_len, smoke=smoke)
    cells = cell_study(pair, n_requests=10 if smoke else n_requests,
                       prompt_len=prompt_len, min_new=min_new,
                       max_new=max_new, rate=max(rates), method=method,
                       ecfg=ecfg, t_slm=t_slm, t_llm=t_llm,
                       cache_len=cache_len)
    transport = transport_study(
        n_requests=8 if smoke else 10, prompt_len=prompt_len,
        min_new=min_new, max_new=min(max_new, 16), rate=max(rates),
        method=method, ecfg=ecfg, t_slm=t_slm, t_llm=t_llm,
        cache_len=cache_len)
    path = common.emit_csv("serve_load", rows, KEYS)
    jpath = os.path.join(os.path.dirname(path), "BENCH_serve.json")
    with open(jpath, "w") as f:
        json.dump({"schema": "BENCH_serve/v1", "smoke": smoke,
                   "t_slm_s": t_slm, "t_llm_s": t_llm,
                   "policy_study": rows, "paged_study": paged}, f,
                  indent=2)
    ppath = os.path.join(os.path.dirname(path), "BENCH_pipeline.json")
    with open(ppath, "w") as f:
        json.dump({"schema": "BENCH_pipeline/v1", "smoke": smoke,
                   "t_slm_s": t_slm, "t_llm_s": t_llm,
                   "pipeline_study": pipe}, f, indent=2)
    wpath = os.path.join(os.path.dirname(path), "BENCH_wire.json")
    with open(wpath, "w") as f:
        json.dump({"schema": "BENCH_wire/v1", "smoke": smoke,
                   "t_slm_s": t_slm, "t_llm_s": t_llm,
                   "wire_study": wire}, f, indent=2)
    cpath = os.path.join(os.path.dirname(path), "BENCH_cells.json")
    with open(cpath, "w") as f:
        json.dump({"schema": "BENCH_cells/v1", "smoke": smoke,
                   "t_slm_s": t_slm, "t_llm_s": t_llm,
                   "cell_study": cells}, f, indent=2)
    tpath = os.path.join(os.path.dirname(path), "BENCH_transport.json")
    with open(tpath, "w") as f:
        json.dump({"schema": "BENCH_transport/v1", "smoke": smoke,
                   "t_slm_s": t_slm, "t_llm_s": t_llm,
                   "transport_study": transport}, f, indent=2)
    return rows, paged, pipe, wire, cells, transport, path, jpath, \
        ppath, wpath, cpath, tpath


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="random-init smoke pair, reduced grid")
    args = ap.parse_args()
    (rows, paged, pipe, wire, cells, transport, path, jpath, ppath,
     wpath, cpath, tpath) = run(smoke=args.smoke)
    for r in rows:
        print(f"{r['policy']:10s} rate={r['rate_rps']:5.1f}/s "
              f"tok/s={r['throughput_tok_s']:7.2f} "
              f"p50={r['latency_p50_s']:6.3f}s "
              f"p99={r['latency_p99_s']:6.3f}s "
              f"reject={r['rejection_rate']:.2f}")
    # headline 1: at the highest load, continuous must not lose to static
    hi = max(r["rate_rps"] for r in rows)
    cont = next(r for r in rows if r["rate_rps"] == hi
                and r["policy"] == "continuous")
    stat = next(r for r in rows if r["rate_rps"] == hi
                and r["policy"] == "static")
    gain = cont["throughput_tok_s"] / max(stat["throughput_tok_s"], 1e-9)
    verdict = "PASS" if gain >= 1.0 else "FAIL"
    print(f"[{verdict}] high-load ({hi}/s) continuous/static "
          f"throughput ratio = {gain:.2f}x")
    # headline 2: same KV budget, paged must beat dense on concurrency
    # without losing throughput (or beat it on peak KV at equal tput)
    pg, ct, v = paged["paged"], paged["contiguous"], paged["verdict"]
    print(f"paged      budget={paged['memory_budget_tokens']} tok "
          f"({paged['page_size']}-tok pages): "
          f"peak_active {ct['peak_active']} -> {pg['peak_active']}, "
          f"tok/s {ct['throughput_tok_s']:.2f} -> "
          f"{pg['throughput_tok_s']:.2f}, "
          f"peak KV {ct['peak_kv_tokens']} -> {pg['peak_kv_tokens']} tok, "
          f"preempted={pg['n_preempted']}")
    print(f"[{'PASS' if v['ok'] else 'FAIL'}-PAGED] paged/contiguous: "
          f"concurrency +{pg['peak_active'] - ct['peak_active']}, "
          f"throughput ratio = {v['throughput_ratio']:.2f}x")
    # headline 3: at the default 1 Mbit/s uplink, the event-driven
    # pipelined schedule must cut mean request latency vs lockstep while
    # emitting bit-identical token streams
    lk, pp, pv = pipe["lockstep"], pipe["pipelined"], pipe["verdict"]
    print(f"pipeline   uplink={pipe['uplink_bps']:.0f}bps "
          f"rate={pipe['rate_rps']}/s: mean latency "
          f"{lk['latency_mean_s']:.3f}s -> {pp['latency_mean_s']:.3f}s "
          f"(x{pv['latency_ratio']:.2f}), makespan "
          f"{lk['makespan_s']:.3f}s -> {pp['makespan_s']:.3f}s, "
          f"spec {pp['n_spec_hits']}h/{pp['n_spec_misses']}m, "
          f"streams_identical={pv['streams_identical']}")
    print(f"[{'PASS' if pv['ok'] else 'FAIL'}-PIPELINED] "
          f"pipelined/lockstep mean latency = {pv['latency_ratio']:.2f}x"
          f" (identical streams: {pv['streams_identical']})")
    # headline 4: the entropy-coded wire must strictly beat fixed-width
    # on uplink bits (every payload), land within 15% of the core/bits
    # entropy reference, and never slow serving down at 1 Mbit/s — with
    # bit-identical token streams across codec versions
    wv = wire["verdict"]
    print(f"wire       V={wire['V']} ell={wire['ell']}: bits/round "
          f"{wire['v1']['uplink_bits_per_round']:.0f} -> "
          f"{wire['v2']['uplink_bits_per_round']:.0f} "
          f"(x{wv['bits_ratio_v2_v1']:.2f}), reference "
          f"{wire['v2']['reference_bits_per_round']:.0f} "
          f"(v2/ref {wv['ratio_to_reference']:.3f}), 1Mbit latency "
          f"x{wv['latency_ratio_1mbit']:.2f}, budget est err "
          f"{wire['budget_study']['analytic_abs_err_bits']:.0f} -> "
          f"{wire['budget_study']['calibrated_abs_err_bits']:.0f} bits")
    print(f"[{'PASS' if wv['ok'] else 'FAIL'}-CODEC] v2/v1 uplink bits "
          f"= {wv['bits_ratio_v2_v1']:.2f}x, v2/reference = "
          f"{wv['ratio_to_reference']:.3f} (<= 1.15), identical streams:"
          f" {wv['streams_identical']}")
    # headline 5: through any number of cells, with or without verdict
    # batching, the streams must match the single-cell reference — and
    # in the downlink-limited regime one coded frame per cell per round
    # must strictly cut downlink bits AND messages vs per-verdict
    # broadcasts
    cv = cells["verdict"]
    for row in cells["topologies"]:
        pv = row["per_verdict_lockstep"]
        bt = row["batched_lockstep"]
        print(f"cells={row['n_cells']}  downlink="
              f"{cells['downlink_bps']:.0f}bps: bits/round "
              f"{pv['downlink_bits_per_round']:.0f} -> "
              f"{bt['downlink_bits_per_round']:.0f} "
              f"(x{row['verdict']['downlink_bits_ratio']:.2f}), msgs "
              f"{pv['downlink_msgs']} -> {bt['downlink_msgs']}, "
              f"makespan {pv['makespan_s']:.3f}s -> "
              f"{bt['makespan_s']:.3f}s")
    ratios = ", ".join(f"{r:.2f}x" for r in cv["bits_ratios"])
    print(f"[{'PASS' if cv['ok'] else 'FAIL'}-CELLS] batched/per-verdict"
          f" downlink bits/round = [{ratios}] (identical streams: "
          f"{cv['streams_identical']})")
    # headline 6: real sockets vs the simulator — the SAME seeded trace
    # through a threaded CloudServer must emit bit-identical streams in
    # both pipeline modes, with measured wall-clock reported next to
    # the sim's modeled clock
    tv = transport["verdict"]
    for mode, row in transport["modes"].items():
        rpc = row["tcp_measured"]["rpc_round_s"]
        print(f"transport  {mode:9s} cells={transport['n_cells']}: "
              f"rpc mean={rpc['mean']*1e3:.1f}ms "
              f"p95={rpc['p95']*1e3:.1f}ms "
              f"({row['tcp_measured']['n_verify_rpcs']} RPCs), makespan "
              f"sim {row['sim_modeled']['makespan_s']:.3f}s (modeled) / "
              f"tcp {row['tcp_measured']['makespan_s']:.3f}s (measured), "
              f"identical={row['streams_identical']}")
    print(f"[{'PASS' if tv['ok'] else 'FAIL'}-TRANSPORT] tcp == sim "
          f"token streams over real sockets (lockstep & pipelined: "
          f"{tv['streams_identical']})")
    print("->", path)
    print("->", jpath)
    print("->", ppath)
    print("->", wpath)
    print("->", cpath)
    print("->", tpath)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
