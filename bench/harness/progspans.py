"""The program's own spans and counters, reduced for the benchmark.

The program (``repro.obs``) records, when it is handed an enabled
``Obs`` (a traced run hands one to its ``ServeSession``), wall spans
around the step programs and the host work between them (``edge.*``,
``cloud.*``, ``verdict.*``), each mirrored into a ``jax.profiler`` trace
as a ``TraceAnnotation``, and counters at the same boundaries
(``wire.*``, ``edge.rows_*``, ``serve.*``).  ``harness.cell.Records``
keeps the window's spans (``in_window``) and the counters' increments
over it (``deltas``); the metric readers reduce them with the functions
here, each on plain tuples and dicts.  The device-trace side (idle time
by span, named-scope device time) is ``harness.tracefile``'s.

A program without these spans or counters gives None from every reader.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional

from harness.tracefile import _union

STEP_SPANS = ("edge.step", "cloud.step")
CODEC_SPANS = ("edge.pack", "cloud.unpack", "verdict.pack", "verdict.unpack")
HANDOFF_SPANS = ("edge.fetch", "cloud.upload")


# -- spans on the program's own clock ---------------------------------------
def in_window(spans: Iterable[tuple], t_open: float,
              t_close: float) -> List[tuple]:
    """``SpanTracer.wall_spans()`` tuples (name, t0, t1, id, parent,
    args) that start inside the window."""
    return [s for s in spans if t_open <= s[1] < t_close]


def coverage(spans: List[tuple], calls: List[tuple]) -> Optional[float]:
    """Share of the engine calls' wall time outside the step programs
    that the program's leaf spans (those with no child) cover, summed
    over the calls.  ``calls``: (kind, t0, t1, t_step, ...) as the
    harness records them."""
    parents = {s[4] for s in spans}
    leaves = sorted((s[1], s[2]) for s in spans
                    if s[3] not in parents and s[0] not in STEP_SPANS)
    off, cov = 0.0, 0.0
    for c in calls:
        t0, t1 = c[1], c[2]
        off += (t1 - t0) - c[3]
        cov += _union([(max(a, t0), min(b, t1)) for a, b in leaves
                       if a < t1 and b > t0])
    return cov / off if off > 0 else None


def _share(spans, names, window_s) -> Optional[float]:
    t = sum(s[2] - s[1] for s in spans or () if s[0] in names)
    return t / window_s * 100 if t > 0 and window_s > 0 else None


def codec_share(spans, window_s) -> Optional[float]:
    """Wire codec (uplink encode and decode, verdict pack and unpack) as
    a share of the window's wall time, %."""
    return _share(spans, CODEC_SPANS, window_s)


def handoff_share(spans, window_s) -> Optional[float]:
    """The q-hat handoff (the edge's device->host reads, the cloud's
    dense q-hat upload) as a share of the window's wall time, %."""
    return _share(spans, HANDOFF_SPANS, window_s)


def sqs_step_ms(scopes: Optional[Dict[str, float]],
                programs: dict) -> Optional[float]:
    """SQS device time per draft step program run, ms, from the draft
    program's ``scope_dev_s`` and the trace's ``programs``."""
    n = programs.get("jit__draft_round", {}).get("n", 0)
    if not scopes or not scopes.get("sqs") or not n:
        return None
    return scopes["sqs"] / n * 1e3


# -- counters ---------------------------------------------------------------
def _flat(snap: dict) -> Dict[str, float]:
    out = dict(snap.get("counters", {}))
    for k, h in snap.get("histograms", {}).items():
        out[k + ".count"], out[k + ".sum"] = h["count"], h["sum"]
    return out


def deltas(snap_open: dict, snap_close: dict) -> Dict[str, float]:
    """Counter (and histogram count and sum) increments between two
    ``MetricsRegistry.snapshot()`` readings."""
    a, b = _flat(snap_open), _flat(snap_close)
    return {k: v - a.get(k, 0) for k, v in b.items()}


def _ratio(d, num, den, scale=1.0) -> Optional[float]:
    if not d or not d.get(den) or num not in d:
        return None
    return d[num] / d[den] * scale


def support_k_mean(d) -> Optional[float]:
    """Retained support tokens per transmitted draft."""
    return _ratio(d, "wire.support_tokens", "wire.live_drafts")


def draft_len_mean(d) -> Optional[float]:
    """Transmitted drafts (L^t) per payload."""
    return _ratio(d, "wire.live_drafts", "wire.payloads")


def draft_row_share(d) -> Optional[float]:
    """Rows some slot asked for over rows the draft program computed, %."""
    return _ratio(d, "edge.rows_requested", "edge.rows_computed", 100.0)


def tok_per_slot_round(d) -> Optional[float]:
    """Tokens a request took per applied verdict (slot-round)."""
    return _ratio(d, "serve.tokens_emitted", "serve.rounds_applied")


def slots_per_verify(d) -> Optional[float]:
    """Slots per verify call, from the serving loop's histogram."""
    return _ratio(d, "serve.verify.batch_size.sum",
                  "serve.verify.batch_size.count")
