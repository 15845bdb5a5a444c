"""The reductions of the program's own spans and counters
(``harness.progspans``): idle attribution and named-scope device time
on a synthetic trace, each reader on recorded spans and counters, and
on one small CPU served run the program's counters against the
benchmark's own ``slots_per_verify`` and ``tok_per_slot_round``."""
import time

import pytest

from conftest import tiny_spec
from harness import cell, progspans, session, traffic
from harness.clocks import CompileClock

D, H = "/device:TPU:0", "/host:CPU"
SQS = {"tf_op": "jit(_draft_round)/while/body/sqs/sort"}


def _trace():
    return [
        (D, "XLA Modules", "jit__draft_round(3)", 0, 110, {}),
        (D, "XLA Ops", "while.5", 0, 100, {}),          # container
        (D, "XLA Ops", "sort.9", 10, 30, SQS),          # scope from stats
        (D, "XLA Ops", "fusion.3", 50, 40, {}),         # scope from HLO text
        (D, "XLA Ops", "copy.4", 100, 10, {}),          # under no scope
        (D, "XLA Modules", "jit__verify_round(4)", 400, 20, {}),
        (D, "XLA Ops", "fusion.1", 400, 20, {}),
        (D, "XLA Ops", "fusion.2", 700, 10, {}),
        (H, "python", "bench.draft", 100, 550, {}),
        (H, "python", "edge.fetch", 200, 100, {}),      # nested in bench.*
        (H, "python", "edge.pack", 500, 50, {}),
    ]


def test_idle_by_span_prefers_the_program_span():
    idle = dict(progspans.idle_by_span(_trace()))
    # gap 110-400 (midpoint 255): inside edge.fetch inside bench.draft
    assert idle["edge.fetch"] == pytest.approx(290e-9)
    # gap 420-700 (midpoint 560): past edge.pack, still in bench.draft
    assert idle["bench.draft"] == pytest.approx(280e-9)
    assert set(idle) == {"edge.fetch", "bench.draft"}


def test_idle_by_span_falls_back_to_the_host():
    ev = [(D, "XLA Ops", "a", 0, 10, {}), (D, "XLA Ops", "b", 30, 10, {})]
    assert progspans.idle_by_span(ev) == [[progspans.OTHER_HOST, 20e-9]]


HLO = """
  %fusion.3 = bf16[4,1024]{1,0} fusion(%p), kind=kLoop, calls=%c, metadata={op_name="jit(_draft_round)/while/body/closed_call/slm/dot_general" stack_frame_id=3}
  ROOT %copy.4 = f32[4]{0} copy(%x)
"""


def test_hlo_op_names_and_scope_of():
    names = progspans.hlo_op_names(HLO)
    assert names == {"fusion.3": "jit(_draft_round)/while/body/"
                                 "closed_call/slm/dot_general"}
    assert progspans.scope_of("jit(f)/while/body/slm/sqs/jit(sort)/sort") \
        == "sqs"
    assert progspans.scope_of("jit(f)/copy") == progspans.UNSCOPED


def test_scope_dev_s_counts_leaves_once():
    sc = progspans.scope_dev_s(_trace(), op_names=progspans.hlo_op_names(HLO))
    assert sc["sqs"] == pytest.approx(30e-9)
    assert sc["slm"] == pytest.approx(40e-9)
    assert sc[progspans.UNSCOPED] == pytest.approx(10e-9)
    assert sc["_leaf"] == pytest.approx(80e-9)       # the while not again
    assert progspans.scope_dev_s(_trace(), module="jit__nothing") == {}


def _spans():
    # (name, t0, t1, id, parent, args) as SpanTracer.wall_spans() gives
    return [
        ("edge.prep", 10.00, 10.01, 0, -1, {}),
        ("edge.step", 10.01, 10.09, 1, -1, {}),
        ("edge.fetch", 10.09, 10.095, 2, -1, {}),
        ("edge.pack", 10.095, 10.10, 3, -1, {}),
        ("cloud.unpack", 10.20, 10.21, 4, -1, {}),
        ("cloud.upload", 10.21, 10.23, 5, -1, {}),
        ("cloud.step", 10.23, 10.25, 6, -1, {}),
        ("verdict.pack", 10.25, 10.26, 7, -1, {}),
        ("edge.admit", 12.0, 12.5, 8, -1, {}),    # outside the window
    ]


def test_span_readers():
    spans = progspans.in_window(_spans(), 10.0, 11.0)
    assert len(spans) == 8
    assert progspans.codec_share(spans, 1.0) == pytest.approx(2.5)
    assert progspans.handoff_share(spans, 1.0) == pytest.approx(2.5)
    assert progspans.codec_share([], 1.0) is None
    calls = [("draft", 10.0, 10.11, 0.08), ("verify", 10.2, 10.25, 0.02)]
    # the draft call's 0.03 s off the step: 0.02 covered; verify's 0.03: all
    assert progspans.coverage(spans, calls) == pytest.approx(0.05 / 0.06)


def test_coverage_counts_leaf_spans_only():
    spans = [("edge.draft", 0.0, 1.0, 0, -1, {}),
             ("edge.step", 0.1, 0.5, 1, 0, {}),
             ("edge.pack", 0.5, 0.7, 2, 0, {})]
    assert progspans.coverage(spans, [("draft", 0.0, 1.0, 0.4)]) == \
        pytest.approx(0.2 / 0.6)


def test_counter_readers():
    a = {"counters": {"wire.payloads": 2, "wire.live_drafts": 10,
                      "wire.support_tokens": 100, "edge.rows_requested": 3,
                      "edge.rows_computed": 12},
         "histograms": {"serve.verify.batch_size": {"count": 2, "sum": 3.0}}}
    b = {"counters": {"wire.payloads": 6, "wire.live_drafts": 30,
                      "wire.support_tokens": 500, "edge.rows_requested": 7,
                      "edge.rows_computed": 28, "serve.rounds_applied": 4,
                      "serve.tokens_emitted": 5},
         "histograms": {"serve.verify.batch_size": {"count": 5, "sum": 9.0}}}
    d = progspans.deltas(a, b)
    assert progspans.support_k_mean(d) == pytest.approx(20.0)
    assert progspans.draft_len_mean(d) == pytest.approx(5.0)
    assert progspans.draft_row_share(d) == pytest.approx(25.0)
    assert progspans.tok_per_slot_round(d) == pytest.approx(1.25)
    assert progspans.slots_per_verify(d) == pytest.approx(2.0)
    assert progspans.sqs_step_ms({"sqs": 0.6}, {"jit__draft_round":
                                               {"n": 10}}) == pytest.approx(60)


def test_readers_find_nothing_to_read():
    """What a program without the spans, counters or scopes gives."""
    d = progspans.deltas({}, {"counters": {"serve.drafts": 3}})
    for f in (progspans.support_k_mean, progspans.draft_len_mean,
              progspans.draft_row_share, progspans.tok_per_slot_round,
              progspans.slots_per_verify):
        assert f(d) is None
    assert progspans.sqs_step_ms({}, {}) is None
    assert progspans.coverage([], []) is None


def test_program_counters_reproduce_the_benchmarks_readers():
    """On one small CPU served run with the program's Obs attached, its
    own counters give the benchmark's slots_per_verify and
    tok_per_slot_round over the same window."""
    from repro.obs import Obs
    seed = 20240612
    spec = tiny_spec(slots=3)
    tc, dc, tw, dw = session.build_models(spec["config"]["model"],
                                          spec["config"]["draft"], 11, 12)
    run = session.ServedRun(spec["cell"], spec["traffic"], tc, dc, tw, dw,
                            seed, 2.0, CompileClock())
    obs = Obs.on()
    progspans.attach(run, obs)
    run.warm()
    run.run(traffic.make_trace(spec["traffic"], tc.vocab, seed,
                               spec["traffic"]["n_requests"]))
    rec = cell.Records(run, spec, time.perf_counter(), None, None)
    d = progspans.deltas(run.obs_snap["open"], run.obs_snap["closed"])
    assert progspans.slots_per_verify(d) == pytest.approx(
        cell.reader("slots_per_verify")(rec))
    assert progspans.tok_per_slot_round(d) == pytest.approx(
        cell.reader("tok_per_slot_round")(rec))
    spans = progspans.in_window(obs.tracer.wall_spans(), rec.t_open,
                                rec.t_close)
    assert {"edge.step", "edge.fetch", "edge.pack", "cloud.step",
            "cloud.upload"} <= {s[0] for s in spans}
    assert 0 < progspans.codec_share(spans, rec.window_s) < 100
    assert progspans.draft_row_share(d) == pytest.approx(100 / 3)
    assert progspans.support_k_mean(d) > 0
    assert 1 <= progspans.draft_len_mean(d) <= spec["cell"]["engine"]["L_max"]
    assert progspans.coverage(spans, rec.calls) > 0.5
