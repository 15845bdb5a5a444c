"""The program's own readings: idle attribution and named-scope device
time of both step programs on a synthetic trace (``harness.tracefile``),
``Records`` carrying them, each span and counter reader
(``harness.progspans``), and on small CPU served runs the program's
counters against the benchmark's own ``slots_per_verify`` and
``tok_per_slot_round``, and a whole traced run reporting the six
readings that exist on the CPU."""
import time
import types

import jax
import pytest

from conftest import tiny_spec
from harness import cell, kinds, progspans, session, tracefile, traffic
from harness.clocks import CompileClock

D, H = "/device:TPU:0", "/host:CPU"


def _trace():
    return [
        (D, "XLA Modules", "jit__draft_round(3)", 0, 110),
        (D, "XLA Ops", "while.5", 0, 100),          # container
        (D, "XLA Ops", "sort.9", 10, 30),           # sqs
        (D, "XLA Ops", "fusion.3", 50, 40),         # slm
        (D, "XLA Ops", "copy.4", 100, 10),          # under no scope
        (D, "XLA Modules", "jit__verify_round(4)", 400, 30),
        (D, "XLA Ops", "fusion.1", 400, 20),        # llm
        (D, "XLA Ops", "%fusion.2 = f32[4] fusion(%a)", 420, 10),  # verify
        (D, "XLA Ops", "fusion.7", 700, 10),        # in no program
        (H, "python", "bench.draft", 100, 550),
        (H, "python", "edge.fetch", 200, 100),      # nested in bench.*
        (H, "python", "edge.pack", 500, 50),
    ]


DRAFT_HLO = """
  %sort.9 = f32[4,1024]{1,0} sort(%p), metadata={op_name="jit(_draft_round)/while/body/sqs/sort" stack_frame_id=2}
  %fusion.3 = bf16[4,1024]{1,0} fusion(%p), kind=kLoop, calls=%c, metadata={op_name="jit(_draft_round)/while/body/closed_call/slm/dot_general" stack_frame_id=3}
  ROOT %copy.4 = f32[4]{0} copy(%x)
"""
VERIFY_HLO = """
  %fusion.1 = bf16[4,1024]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(_verify_round)/llm/dot_general"}
  ROOT %fusion.2 = f32[4]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(_verify_round)/verify/reduce_max"}
"""


def _names():
    return {"jit__draft_round": tracefile.hlo_op_names(DRAFT_HLO),
            "jit__verify_round": tracefile.hlo_op_names(VERIFY_HLO)}


def test_idle_by_span_prefers_the_program_span():
    idle = dict(tracefile.reduce(_trace(), (0, 1))["idle_gaps"])
    # gap 110-400 (midpoint 255): inside edge.fetch inside bench.draft
    assert idle["edge.fetch"] == pytest.approx(290e-9)
    # gap 430-700 (midpoint 565): past edge.pack, still in bench.draft
    assert idle["bench.draft"] == pytest.approx(270e-9)
    assert set(idle) == {"edge.fetch", "bench.draft"}


def test_idle_by_span_falls_back_to_the_host():
    ev = [(D, "XLA Ops", "a", 0, 10), (D, "XLA Ops", "b", 30, 10)]
    assert tracefile.reduce(ev, (0, 1))["idle_gaps"] == \
        [[tracefile.OTHER_HOST, 20e-9]]


def test_hlo_op_names_and_scope_of():
    names = tracefile.hlo_op_names(DRAFT_HLO)
    assert names == {
        "sort.9": "jit(_draft_round)/while/body/sqs/sort",
        "fusion.3": "jit(_draft_round)/while/body/closed_call/slm/"
                    "dot_general"}
    assert tracefile.scope_of("jit(f)/while/body/slm/sqs/jit(sort)/sort") \
        == "sqs"
    assert tracefile.scope_of("jit(f)/copy") == tracefile.UNSCOPED


def test_scope_dev_s_counts_leaves_once():
    sc = tracefile.reduce(_trace(), (0, 1), _names())["scope_dev_s"]
    d, v = sc["jit__draft_round"], sc["jit__verify_round"]
    assert d["sqs"] == pytest.approx(30e-9)
    assert d["slm"] == pytest.approx(40e-9)
    assert d[tracefile.UNSCOPED] == pytest.approx(10e-9)
    assert d["_leaf"] == pytest.approx(80e-9)       # the while not again
    assert v == pytest.approx({"llm": 20e-9, "verify": 10e-9,
                               "_leaf": 30e-9})
    # a program given no compiled text, or that did not run, has none
    assert set(tracefile.reduce(_trace(), (0, 1))["scope_dev_s"]) == set()
    assert "jit__nothing" not in tracefile.reduce(
        _trace(), (0, 1), {"jit__nothing": {}})["scope_dev_s"]


def test_records_carry_both_programs_scope_times():
    run = types.SimpleNamespace(
        target=None, draft=None, L=4, t_open=0.0, t_close=1e-6,
        now_open=0.0, now_close=1.0, deliveries=[], calls=[],
        admissions=[], link_open=(0, 0), link_close=(0, 0), obs=None)
    red = tracefile.reduce(_trace(), (0.0, 1e-6), _names())
    rec = cell.Records(run, {"cell": {}, "traffic": {}}, 1.0, red, None)
    assert rec.scope_dev_s["jit__draft_round"]["sqs"] == pytest.approx(30e-9)
    assert rec.scope_dev_s["jit__verify_round"]["llm"] == pytest.approx(20e-9)
    assert rec.spans is None and rec.counters is None
    assert cell.reader("sqs_step_ms")(rec) == pytest.approx(30e-9 * 1e3)


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
    assert progspans.codec_share(None, 1.0) is None
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
        assert f(None) is None
    assert progspans.sqs_step_ms({}, {}) is None
    assert progspans.sqs_step_ms(None, {"jit__draft_round": {"n": 2}}) is None
    assert progspans.coverage([], []) is None


def test_program_counters_reproduce_the_benchmarks_readers():
    """On one small CPU served run with the program's Obs handed to its
    ServeSession, its own counters give the benchmark's slots_per_verify
    and tok_per_slot_round over the same window."""
    from repro.obs import Obs
    seed = 20240612
    spec = tiny_spec(slots=3)
    target, draft = kinds.build(spec["config"], 11, 12)
    run = session.ServedRun(spec["cell"], spec["traffic"], target, draft,
                            seed, 2.0, CompileClock(), obs=Obs.on())
    run.warm()
    run.run(traffic.make_trace(spec["traffic"], target.cfg.vocab, seed,
                               spec["traffic"]["n_requests"]))
    rec = cell.Records(run, spec, time.perf_counter(), None, None)
    d = rec.counters
    assert progspans.slots_per_verify(d) == pytest.approx(
        cell.reader("slots_per_verify")(rec))
    assert progspans.tok_per_slot_round(d) == pytest.approx(
        cell.reader("tok_per_slot_round")(rec))
    assert {"edge.step", "edge.fetch", "edge.pack", "cloud.step",
            "cloud.upload"} <= {s[0] for s in rec.spans}
    assert 0 < progspans.codec_share(rec.spans, rec.window_s) < 100
    assert progspans.draft_row_share(d) == pytest.approx(100 / 3)
    assert progspans.support_k_mean(d) > 0
    assert 1 <= progspans.draft_len_mean(d) <= spec["cell"]["engine"]["L_max"]
    assert progspans.coverage(rec.spans, rec.calls) > 0.5


SIX = ("codec_share", "handoff_share", "sqs_step_ms", "support_k_mean",
       "draft_len_mean", "draft_row_share")


def test_a_traced_run_reports_the_program_readings():
    """The whole traced path on the CPU: the Obs reaches the program,
    Records carry its spans and counters, both step programs' compiled
    text is read, and the per-layer line has every reading that exists
    here.  ``sqs_step_ms`` reads device ops, and the CPU has no TPU
    plane: it is left out, not 0."""
    spec = tiny_spec(slots=3)
    spec["per_layer"] = [{"name": n, "unit": "x"} for n in SIX]
    res, rows = cell.run(spec, 20240613, 60.0, True, time.perf_counter(),
                         jax.devices())
    assert res["correct"], rows
    got = res["metrics"]
    assert set(got) == set(SIX) - {"sqs_step_ms"}, got
    assert 0 < got["codec_share"]["value"] < 100
    assert 0 < got["handoff_share"]["value"] < 100
    assert got["draft_row_share"]["value"] == pytest.approx(100 / 3)
    assert res["device"]["window_s"] > 0
    assert "idle_gaps" in res["breakdown"]
