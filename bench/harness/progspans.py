"""The program's own spans and counters, reduced for the benchmark.

The program (``repro.obs``) records, when it is handed an enabled
``Obs``, wall spans around the step programs and the host work between
them (``edge.*``, ``cloud.*``, ``verdict.*``), each mirrored into a
``jax.profiler`` trace as a ``TraceAnnotation``, and counters at the
same boundaries (``wire.*``, ``edge.rows_*``, ``serve.*``).  Its draft
and verify step programs carry named scopes (``slm``/``sqs``,
``llm``/``verify``) in their HLO ``op_name`` metadata.  This module
turns those into numbers; each function works on plain tuples and
dicts, so it can be checked on a synthetic trace.

  load          a profiler trace as (plane, line, name, start_ns,
                dur_ns, stats), keeping the stats of device ops;
  idle_by_span  device idle time attributed to the innermost program
                span covering each gap's midpoint, else the innermost
                ``bench.*`` annotation, else the host's other work;
  scope_dev_s   a step program's leaf-op device time by named scope;
  coverage      the share of the engine calls' wall time outside the
                step programs that the program's leaf spans account for;
  attach        hands an enabled ``Obs`` to a ``ServedRun`` and reads
                the counters at the window's open and close;
  the readers   ``codec_share``, ``handoff_share``, ``sqs_step_ms``,
                ``support_k_mean``, ``draft_len_mean``,
                ``draft_row_share``.

A program without these spans or counters gives None from every reader.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional

from harness.tracefile import DEVICE_PLANE, MODULE_LINE, OPS_LINE, \
    _gaps, _union, module_name

PROGRAM_PREFIXES = ("edge.", "cloud.", "verdict.")
STEP_SPANS = ("edge.step", "cloud.step")
CODEC_SPANS = ("edge.pack", "cloud.unpack", "verdict.pack", "verdict.unpack")
HANDOFF_SPANS = ("edge.fetch", "cloud.upload")
SCOPES = ("slm", "sqs", "llm", "verify")
UNSCOPED = "(unscoped)"
OTHER_HOST = "host (serving loop, codec, scheduling)"


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


# -- the profiler trace ---------------------------------------------------
def load(trace_dir: str) -> List[tuple]:
    """Like ``tracefile.load``, with a sixth field: the stats of the
    device planes' op events as a dict (empty elsewhere)."""
    from jax.profiler import ProfileData
    out = []
    for f in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True):
        for plane in ProfileData.from_file(f).planes:
            dev = bool(DEVICE_PLANE.match(plane.name))
            for line in plane.lines:
                keep = dev and line.name == OPS_LINE
                for e in line.events:
                    out.append((plane.name, line.name, e.name,
                                int(e.start_ns), int(e.duration_ns),
                                dict(e.stats) if keep else {}))
    return out


def _first_device(events) -> Optional[str]:
    devs = sorted({e[0] for e in events if DEVICE_PLANE.match(e[0])})
    return devs[0] if devs else None


def idle_by_span(events) -> List[list]:
    """Device idle seconds by what the host was doing, the 10 largest:
    the innermost program span covering each idle gap's midpoint, or
    else the innermost ``bench.*`` annotation, or else the host's other
    work.  ``tracefile.reduce``'s ``idle_gaps`` is the same with
    ``bench.*`` annotations alone."""
    dev = _first_device(events)
    dev_iv = [(e[3], e[3] + e[4]) for e in events
              if e[0] == dev and e[1] == OPS_LINE]
    prog, bench = [], []
    for e in events:
        if DEVICE_PLANE.match(e[0]):
            continue
        if is_program_span(e[2]):
            prog.append((e[3], e[3] + e[4], e[2]))
        elif e[2].startswith("bench."):
            bench.append((e[3], e[3] + e[4], e[2]))
    gaps = _gaps(dev_iv)
    mids = [(g0 + g1) / 2 for g0, g1 in gaps]
    idle: Dict[str, int] = {}
    for (g0, g1), p, b in zip(gaps, _innermost(prog, mids),
                              _innermost(bench, mids)):
        name = p or b or OTHER_HOST
        idle[name] = idle.get(name, 0) + (g1 - g0)
    return [[k, v / 1e9] for k, v in
            sorted(idle.items(), key=lambda kv: -kv[1])[:10]]


def _innermost(spans: List[tuple], points: List[float]) -> List[Optional[str]]:
    """For each of the ascending ``points``, the name of the shortest
    (start, end, name) span covering it, or None."""
    spans = sorted(spans)
    out, active, i = [], [], 0
    for p in points:
        while i < len(spans) and spans[i][0] <= p:
            active.append(spans[i])
            i += 1
        active = [h for h in active if h[1] >= p]
        out.append(min(active, key=lambda h: h[1] - h[0])[2]
                   if active else None)
    return out


# -- named scopes -----------------------------------------------------------
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                    r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its ``op_name`` metadata, from a compiled
    program's text (``jit(f).lower(...).compile().as_text()``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an op_name path, or UNSCOPED."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def _instr(name: str) -> str:
    return name.split(" = ")[0].lstrip("%")


def _op_scope(name: str, stats: dict, op_names: Dict[str, str]) -> str:
    for v in stats.values():
        if isinstance(v, str) and "/" in v:
            s = scope_of(v)
            if s != UNSCOPED:
                return s
    for key in (stats.get("hlo_op"), _instr(name), _instr(name).lstrip("_")):
        if isinstance(key, str) and key in op_names:
            return scope_of(op_names[key])
    return UNSCOPED


def _leaves(ops: List[tuple]) -> List[tuple]:
    """The ops that contain no other op (drops ``while``/call containers,
    whose time includes their body's)."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    container = set()
    stack: List[int] = []
    for i, (s, e, *_) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            container.add(stack[-1])
        stack.append(i)
    return [o for i, o in enumerate(ops) if i not in container]


def scope_dev_s(events, module: str = "jit__draft_round",
                op_names: Optional[Dict[str, str]] = None) -> Dict[str, float]:
    """Leaf-op device seconds of ``module`` on the first device, by
    named scope (UNSCOPED for ops under none); ``_leaf`` is their sum.
    The scope comes from an op event's stats when the trace carries the
    op_name there, else from ``op_names`` (``hlo_op_names``).  JAX's
    persistent compilation cache keys programs without their debug
    info, where scopes live: an executable cached before a scope was
    added keeps its old op_names, and its ops read UNSCOPED."""
    dev = _first_device(events)
    op_names = op_names or {}
    mods = sorted((e[3], e[3] + e[4], module_name(e[2])) for e in events
                  if e[0] == dev and e[1] == MODULE_LINE)
    if not any(m[2] == module for m in mods):
        return {}
    starts = [m[0] for m in mods]
    ops = []
    for e in events:
        if e[0] == dev and e[1] == OPS_LINE:
            s, t = e[3], e[3] + e[4]
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][1] and mods[i][2] == module:
                ops.append((s, t, e[2], e[5] if len(e) > 5 else {}))
    out: Dict[str, float] = {}
    for s, t, name, stats in _leaves(ops):
        k = _op_scope(name, stats, op_names)
        out[k] = out.get(k, 0.0) + (t - s) / 1e9
    out["_leaf"] = sum(out.values())
    return out


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
    if not spans or window_s <= 0:
        return None
    return sum(s[2] - s[1] for s in spans if s[0] in names) / window_s * 100


def codec_share(spans, window_s) -> Optional[float]:
    """Wire codec (uplink encode and decode, verdict pack and unpack) as
    a share of the window's wall time, %."""
    return _share(spans, CODEC_SPANS, window_s)


def handoff_share(spans, window_s) -> Optional[float]:
    """The q-hat handoff (the edge's device->host reads, the cloud's
    dense q-hat upload) as a share of the window's wall time, %."""
    return _share(spans, HANDOFF_SPANS, window_s)


def sqs_step_ms(scopes: Dict[str, float], programs: dict) -> Optional[float]:
    """SQS device time per draft step program run, ms."""
    n = programs.get("jit__draft_round", {}).get("n", 0)
    if not scopes.get("sqs") or not n:
        return None
    return scopes["sqs"] / n * 1e3


# -- counters ---------------------------------------------------------------
def attach(run, obs) -> None:
    """Hand ``obs`` to a ``harness.session.ServedRun``'s serving session
    and engine (before ``run.run``), and keep ``obs.metrics`` snapshots
    at the window's open and close in ``run.obs_snap``.  A program whose
    engine takes no ``Obs`` ignores the attribute."""
    run.session.obs = obs
    run.engine.obs = obs
    run.obs_snap = {}
    tick = run.tick

    def observed_tick():
        phase = run.phase
        tick()
        if run.phase != phase and run.phase in ("open", "closed"):
            run.obs_snap[run.phase] = obs.metrics.snapshot()
    run.tick = observed_tick


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
