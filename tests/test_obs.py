"""Observability layer tests (repro.obs).

Three layers, mirroring the package:

  * primitives — tracer span nesting + Chrome-trace export round-trip,
    fixed-bucket histogram determinism, the consolidated percentile /
    summary-stat helpers, the disabled (null) fast path;
  * decomposition — ``DecompTracker`` on synthetic round metrics must
    reproduce ``core.theory.thm1_bound_total`` exactly (the telemetry's
    three terms sum to the bound), plus the light-mode coverage path;
  * integration — a small serve run with obs fully on emits the same
    token streams bit for bit as with obs off (ZERO PERTURBATION), its
    modeled clock carries every round phase, and the per-round
    rejection telemetry reconciles.
"""
import json

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import EdgeCloudEngine, EngineConfig, MethodConfig
from repro.core.channel import ChannelConfig
from repro.core.theory import thm1_bound_total, thm1_terms
from repro.models import init_params
from repro.obs import (CLOCK_MODELED, CLOCK_WALL, NULL_OBS, DecompTracker,
                       MetricsRegistry, Obs, SpanTracer, percentile,
                       span_names_by_clock, summary_stats)
from repro.serve import ServeConfig, ServeSession, TraceConfig, \
    poisson_trace


# ----------------------------------------------------------------------
# Stat helpers (consolidation of session._percentile / net._stats)
# ----------------------------------------------------------------------
def test_percentile_report_semantics():
    assert np.isnan(percentile([], 50))
    assert percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert percentile([1.0], 99) == 1.0


def test_summary_stats_json_semantics():
    assert summary_stats([]) == {"mean": 0.0, "p50": 0.0, "p95": 0.0,
                                 "n": 0}
    s = summary_stats([1.0, 2.0, 3.0])
    assert s["n"] == 3 and s["mean"] == 2.0 and s["p50"] == 2.0
    json.dumps(s)        # must be JSON-able as-is


# ----------------------------------------------------------------------
# Metrics primitives
# ----------------------------------------------------------------------
def test_counter_gauge_basics():
    m = MetricsRegistry()
    m.counter("c").inc()
    m.counter("c").inc(4)
    g = m.gauge("g")
    g.set(3.0)
    g.set(1.0)
    snap = m.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == {"value": 1.0, "peak": 3.0}


def test_histogram_snapshot_deterministic():
    """Same observations in any order -> byte-identical snapshot (the
    fixed-bucket contract), including via the registry."""
    xs = [0.0002, 0.005, 0.005, 0.2, 7.0, 100.0]
    snaps = []
    for order in (xs, list(reversed(xs))):
        m = MetricsRegistry()
        m.gauge("later_name")          # creation order must not matter
        h = m.histogram("h")
        for v in order:
            h.observe(v)
        snaps.append(json.dumps(m.snapshot(), sort_keys=True))
    assert snaps[0] == snaps[1]
    h = MetricsRegistry().histogram("h", bounds=(1.0, 2.0))
    for v in (0.5, 1.0, 1.5, 99.0):
        h.observe(v)
    s = h.snapshot()
    # boundary lands in its own bucket (le semantics), overflow in inf
    assert s["buckets"] == {"le_1": 2, "le_2": 1, "inf": 1}
    assert s["count"] == 4 and s["max"] == 99.0


def test_disabled_registry_is_noop_and_shared():
    m = MetricsRegistry(enabled=False)
    c = m.counter("a")
    c.inc(10)
    assert c is m.counter("b")         # shared null instrument
    assert c.value == 0
    m.gauge("g").set(5.0)
    m.histogram("h").observe(1.0)
    assert m.snapshot() == {"counters": {}, "gauges": {},
                            "histograms": {}}
    assert not NULL_OBS.enabled
    assert NULL_OBS.tracer.span("x", 0.0, 1.0) == -1


# ----------------------------------------------------------------------
# Tracer
# ----------------------------------------------------------------------
def _demo_trace() -> SpanTracer:
    t = SpanTracer()
    t.begin("round", 0.0, tid="slot0")
    t.span("draft", 0.0, 0.5, tid="slot0", args={"n": 3})
    t.begin("rpc", 0.5, tid="slot0")
    t.end(0.9, tid="slot0")
    t.instant("spec_hit", 0.9, tid="slot0")
    t.end(1.0, tid="slot0")
    t.span("verify_rpc", 0.1, 0.4, clock=CLOCK_WALL, tid="edge")
    return t


def test_tracer_nesting_and_chrome_export(tmp_path):
    t = _demo_trace()
    path = tmp_path / "trace.json"
    t.export(str(path))
    doc = json.loads(path.read_text())      # round-trips as valid JSON
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert {e["ph"] for e in evs} <= {"M", "X", "i"}
    # both clocks present as named processes; spans land on their pid
    names = span_names_by_clock(doc)
    assert names[CLOCK_MODELED] == {"round", "draft", "rpc", "spec_hit"}
    assert names[CLOCK_WALL] == {"verify_rpc"}
    # nesting: the enclosing "round" span covers [0, 1.0]s in µs
    round_ev = next(e for e in evs if e.get("name") == "round")
    assert round_ev["ts"] == 0.0 and round_ev["dur"] == pytest.approx(1e6)


def test_tracer_deterministic_ids():
    a, b = _demo_trace(), _demo_trace()
    assert json.dumps(a.chrome_trace()) == json.dumps(b.chrome_trace())


def test_tracer_disabled_near_zero():
    t = SpanTracer(enabled=False)
    assert t.begin("x", 0.0) == -1
    assert t.end(1.0) == -1
    assert t.span("y", 0.0, 1.0) == -1
    assert t.instant("z", 0.0) == -1
    assert t.n_events == 0
    assert t.chrome_trace()["traceEvents"] == []


def test_tracer_unclosed_span_fails_export():
    t = SpanTracer()
    t.begin("open", 0.0)
    with pytest.raises(AssertionError):
        t.chrome_trace()


def test_tracer_rejects_unknown_clock():
    with pytest.raises(ValueError):
        SpanTracer().span("x", 0.0, 1.0, clock="lamport")


# ----------------------------------------------------------------------
# Scoped wall spans (engine host work, mirrored into the profiler)
# ----------------------------------------------------------------------
def test_wall_span_disabled_reads_no_clock_and_no_annotation(monkeypatch):
    import time

    def boom(*a, **k):
        raise AssertionError("a disabled wall span touched the clock or "
                             "the profiler")
    monkeypatch.setattr(time, "perf_counter", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    t = SpanTracer(enabled=False)
    a, b = t.wall("edge.step"), NULL_OBS.tracer.wall("cloud.step",
                                                     args={"slots": [0]})
    assert a is b                  # one shared no-op context
    with a, b:
        pass
    assert t.n_events == 0 and t.wall_spans() == []


def test_wall_span_records_parents_args_and_exports(monkeypatch):
    opened = []

    class Ann:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    t = SpanTracer()
    with t.wall("cloud.verify", args={"slots": [0, 2]}):
        with t.wall("cloud.upload", args={"slots": [0, 2]}):
            pass
        with t.wall("cloud.step"):
            pass
    with t.wall("verdict.pack"):
        pass
    assert opened == ["cloud.verify", "cloud.upload", "cloud.step",
                      "verdict.pack"]
    spans = {name: (t0, t1, sid, parent, args)
             for name, t0, t1, sid, parent, args in t.wall_spans()}
    outer = spans["cloud.verify"]
    assert outer[3] == -1 and outer[4]["slots"] == [0, 2]
    for inner in ("cloud.upload", "cloud.step"):
        t0, t1, _, parent, _ = spans[inner]
        assert parent == outer[2]
        assert outer[0] <= t0 <= t1 <= outer[1]
    assert spans["cloud.upload"][4]["slots"] == [0, 2]
    assert spans["verdict.pack"][3] == -1
    assert spans["verdict.pack"][0] >= outer[1]
    names = span_names_by_clock(t.chrome_trace())
    assert names[CLOCK_WALL] == set(spans)


def test_wall_span_lands_on_the_profiler_host_plane(tmp_path):
    import glob
    from jax.profiler import ProfileData
    t = SpanTracer()
    x = jax.numpy.ones((8, 8))
    jax.block_until_ready(x @ x)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.wall("edge.fetch"):
            with t.wall("edge.step"):
                jax.block_until_ready(x @ x)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert files
    host = {e.name for f in files
            for plane in ProfileData.from_file(f).planes
            if plane.name.startswith("/host")
            for line in plane.lines for e in line.events}
    assert {"edge.fetch", "edge.step"} <= host


# ----------------------------------------------------------------------
# Theorem-1 decomposition on synthetic round metrics
# ----------------------------------------------------------------------
def _synthetic_round(rng, B=2, L=3, V=8):
    q = rng.random((B, L, V)).astype(np.float32)
    q /= q.sum(-1, keepdims=True)
    p = rng.random((B, L + 1, V)).astype(np.float32)
    p /= p.sum(-1, keepdims=True)
    live = np.ones((B, L), bool)
    live[1, 2] = False
    return {
        "active": np.array([True, True]),
        "n_accept": np.array([1, 2]),
        "L_live": live.sum(1),
        "beta_row": np.array([1e-3, 2e-3], np.float32),
        "dropped_mean": 0.01,
        "q": q,
        # q_hat == q keeps exact_rej == mismatch <= the bound, like the
        # real sparsifier (whose distortion the other two terms bound)
        "q_hat": q.copy(),
        "p": p,
        "dropped_seq": np.full((B, L + 1), 0.01, np.float32),
        "K_seq": np.full((B, L), 16, np.int32),
        "live_seq": live,
    }


def test_decomp_matches_thm1_bound_total():
    rng = np.random.default_rng(3)
    d = DecompTracker(alpha=0.01, eta=0.05, ell=100)
    m = _synthetic_round(rng)
    rec = d.observe_round(m)
    live = m["live_seq"]
    L = live.shape[1]
    terms = thm1_terms(m["q"][live], m["p"][:, :L][live],
                       m["q_hat"][live], m["dropped_seq"][:, :L][live],
                       m["K_seq"][live], 100)
    exact, ub = thm1_bound_total(terms)
    assert rec["n_positions"] == int(live.sum())
    assert rec["bound"] == pytest.approx(float(ub))
    assert rec["exact"] == pytest.approx(float(exact))
    assert rec["mismatch"] + rec["dropped"] + rec["lattice"] == \
        pytest.approx(rec["bound"], abs=1e-5)
    assert rec["distortion"] == rec["dropped"] + rec["lattice"]
    ok, err = d.reconcile()
    assert ok and err < 1e-4
    json.dumps(d.snapshot())


def test_decomp_light_mode_and_coverage():
    d = DecompTracker(alpha=0.01, eta=0.05, ell=100)
    assert d.observe_round({"active": np.array([False])}) is None
    m = {"active": np.array([True, False]),
         "n_accept": np.array([2, 0]),
         "L_live": np.array([3, 0]),
         "beta_row": np.array([5e-3, 1e-3]),
         "dropped_mean": 0.02}
    rec = d.observe_round(m)
    assert rec["n_positions"] == 3 and "bound" not in rec
    assert rec["beta_mean"] == pytest.approx(5e-3)
    cov = d.coverage()
    assert cov["n_positions"] == 3
    assert cov["mean_dropped"] == pytest.approx(0.02)
    assert cov["deviation"] == pytest.approx(0.01)
    assert cov["beta_min"] == cov["beta_max"] == pytest.approx(5e-3)
    lo, hi = cov["beta_envelope"]
    assert lo <= hi
    ok, _ = d.reconcile()
    assert not ok          # light rounds only: nothing to reconcile


# ----------------------------------------------------------------------
# Integration: zero perturbation + reconciliation on a real serve run
# ----------------------------------------------------------------------
METHOD = MethodConfig("csqs", alpha=5e-3, eta=5e-2)


@pytest.fixture(scope="module")
def pair():
    tc = configs.smoke_variant(configs.get_config("qwen2.5-3b"))
    dc = configs.draft_variant(tc, 2)
    tp = init_params(tc, jax.random.PRNGKey(1))
    dp = init_params(dc, jax.random.PRNGKey(2))
    return dc, dp, tc, tp


ENGINE_SPANS = {"edge.prep", "edge.step", "edge.fetch", "edge.pack",
                "cloud.unpack", "cloud.upload", "cloud.step",
                "cloud.commit", "verdict.pack", "verdict.unpack",
                "edge.admit", "cloud.admit"}


def _serve(pair, obs, pipeline="lockstep", eng_hook=None):
    dc, dp, tc, tp = pair
    eng = EdgeCloudEngine(
        dc, dp, tc, tp, METHOD,
        EngineConfig(L_max=3, collect_theory=obs is not None
                     and obs.decomp is not None),
        ChannelConfig(), seed=0)
    if eng_hook is not None:
        eng_hook(eng)
    trace = poisson_trace(TraceConfig(
        n_requests=4, rate_rps=8.0, prompt_len=10, min_new_tokens=3,
        max_new_tokens=6, vocab=tc.vocab, seed=5))
    sess = ServeSession(eng, ServeConfig(
        max_batch=2, cache_len=64, t_slm_s=0.01, t_llm_s=0.02,
        pipeline=pipeline), obs=obs)
    rep = sess.run_trace(trace)
    return {r.rid: tuple(r.tokens) for r in rep.requests}, sess


def test_serve_obs_zero_perturbation_and_reconcile(pair):
    ref, _ = _serve(pair, None)
    obs = Obs.on(decomp=DecompTracker(METHOD.alpha, METHOD.eta,
                                      METHOD.ell))
    streams, sess = _serve(pair, obs)
    # the load-bearing invariant: tracing + metrics + decomposition on
    # or off, the emitted token streams are bit-identical
    assert streams == ref
    names = span_names_by_clock(obs.tracer.chrome_trace())
    assert {"draft", "uplink", "verify",
            "downlink"} <= names[CLOCK_MODELED]
    # the session handed its Obs to the engine: the host work between
    # the step programs is spanned on the wall clock
    assert sess.engine.obs is obs
    assert ENGINE_SPANS <= names[CLOCK_WALL]
    # edge.step / cloud.step are the t_slm / t_llm intervals, one per call
    steps = [s for s in obs.tracer.wall_spans() if s[0] == "edge.step"]
    assert len(steps) == sess.n_rounds
    ok, err = obs.decomp.reconcile()
    assert ok, f"thm1 telemetry failed to reconcile (max err {err})"
    cov = obs.decomp.coverage()
    assert cov["n_positions"] > 0 and np.isfinite(cov["mean_dropped"])
    snap = obs.metrics.snapshot()
    assert snap["counters"]["serve.rounds"] == sess.n_rounds
    # snapshot_topology folded the cell's links + scheduler in
    assert snap["counters"]["serve.cell0.sched.admitted"] == \
        sess.topo.n_admitted
    assert snap["counters"]["serve.cell0.uplink.msgs"] == \
        sess.topo.cells[0].uplink.n_msgs
    json.dumps(snap)       # the --metrics-out artifact is plain JSON


def test_engine_counters_match_the_payloads(pair):
    """wire.* and edge.rows_* equal counts made by hand from what a
    pipelined served trace put on the wire; serve.rounds_applied and
    serve.tokens_emitted equal the requests' own rounds and tokens."""
    seen = {"payloads": [], "rows": 0, "calls": 0}

    def hook(eng):
        draft_slots, spec = eng.draft_slots, eng.draft_speculative_slot
        verify_slots = eng.verify_slots

        def w_draft(slots):
            seen["calls"] += 1
            seen["rows"] += len(slots)
            return draft_slots(slots)

        def w_spec(slot, rec):
            out = spec(slot, rec)
            if out is not None:
                seen["calls"] += 1
                seen["rows"] += 1
            return out

        def w_verify(packed):
            seen["payloads"] += [
                eng.fmt.unpack_draft(b, codec=eng.cloud.slot_codec[s])
                for s, b in packed.items()]
            return verify_slots(packed)
        eng.draft_slots, eng.draft_speculative_slot = w_draft, w_spec
        eng.verify_slots = w_verify
        seen["eng"] = eng

    ref, _ = _serve(pair, None, pipeline="pipelined")
    obs = Obs.on()
    streams, sess = _serve(pair, obs, pipeline="pipelined", eng_hook=hook)
    assert streams == ref
    eng, payloads = seen["eng"], seen["payloads"]
    c = obs.metrics.snapshot()["counters"]
    assert c["wire.payloads"] == len(payloads) > 0
    assert c["wire.live_drafts"] == sum(len(p.tokens) for p in payloads)
    assert c["wire.support_tokens"] == sum(
        len(sup) for p in payloads for sup in p.supports)
    assert c["edge.rows_requested"] == seen["rows"]
    assert c["edge.rows_computed"] == seen["calls"] * eng.B
    reqs = sess.topo.finished
    assert c["serve.rounds_applied"] == sum(r.n_rounds for r in reqs)
    assert c["serve.tokens_emitted"] == sum(r.n_tokens for r in reqs)
    names = span_names_by_clock(obs.tracer.chrome_trace())
    assert ENGINE_SPANS | {"edge.apply"} <= names[CLOCK_WALL]
