"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

``load`` flattens the ``.xplane.pb`` under a trace directory, read with
``jax.profiler.ProfileData``, to (plane, line, name, start_ns, dur_ns)
tuples; ``reduce`` works on such tuples alone, so it can be checked on
a small synthetic trace.

  busy_s      union of the intervals in which an operation ran on the
              device, averaged over the devices that ran any;
  window_s    the traced window's length on the host clock;
  programs    per XLA module name (numeric suffixes dropped): how many
              times it ran and its summed device time;
  device_ops  the 10 device operations that took most time, named
              "<module>/<HLO instruction>";
  idle_gaps   device idle time attributed to what the host was doing:
              the innermost of the program's own spans (``edge.*``,
              ``cloud.*``, ``verdict.*``, annotations of a traced run's
              ``Obs``) covering each gap's midpoint, else the innermost
              ``bench.*`` annotation, else the host's other work; summed
              by name, the 10 largest;
  scope_dev_s per step program given its compiled HLO text: leaf-op
              device seconds by named scope (``slm``/``sqs`` in the
              draft program, ``llm``/``verify`` in the verify program).
              The op -> scope map comes from the compiled text's
              ``op_name`` metadata, not from the trace's.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
PROGRAM_PREFIXES = ("edge.", "cloud.", "verdict.")
OTHER_HOST = "host (serving loop, codec, scheduling)"
SCOPES = ("slm", "sqs", "llm", "verify")
UNSCOPED = "(unscoped)"


def load(trace_dir: str):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return []
    out = []
    for f in files:
        pd = ProfileData.from_file(f)
        for plane in pd.planes:
            for line in plane.lines:
                for e in line.events:
                    out.append((plane.name, line.name, e.name,
                                int(e.start_ns), int(e.duration_ns)))
    return out


def _union(intervals):
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _gaps(intervals):
    out, end = [], None
    for s, e in sorted(intervals):
        if end is not None and s > end:
            out.append((end, s))
        end = e if end is None else max(end, e)
    return out


def module_name(name: str) -> str:
    return re.sub(r"[\(\.]\d+\)?$", "", name.split("(")[0]).strip()


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


def _idle_by_span(events, dev_iv) -> Dict[str, int]:
    prog, bench = [], []
    for p, l, n, s, d in events:
        if DEVICE_PLANE.match(p):
            continue
        if n.startswith(PROGRAM_PREFIXES):
            prog.append((s, s + d, n))
        elif n.startswith("bench."):
            bench.append((s, s + d, n))
    gaps = _gaps(dev_iv)
    mids = [(g0 + g1) / 2 for g0, g1 in gaps]
    idle: Dict[str, int] = {}
    for (g0, g1), pn, bn in zip(gaps, _innermost(prog, mids),
                                _innermost(bench, mids)):
        name = pn or bn or OTHER_HOST
        idle[name] = idle.get(name, 0) + (g1 - g0)
    return idle


# -- named scopes ---------------------------------------------------------------
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


def _scopes(ops: List[tuple], op_names: Dict[str, str]) -> Dict[str, float]:
    """Leaf-op device seconds by named scope (UNSCOPED for ops under
    none, or not in ``op_names``); ``_leaf`` is their sum."""
    out: Dict[str, float] = {}
    for s, t, name in _leaves(ops):
        instr = name.split(" = ")[0].lstrip("%")
        k = scope_of(op_names.get(instr)
                     or op_names.get(instr.lstrip("_"), ""))
        out[k] = out.get(k, 0.0) + (t - s) / 1e9
    out["_leaf"] = sum(out.values())
    return out


def reduce(events, span, op_names: Optional[Dict[str, Dict[str, str]]]
           = None) -> dict:
    """``span``: (t_start, t_stop) of the traced window on the host's
    perf_counter clock (only its length is used).  ``op_names``: per
    step program (XLA module name), ``hlo_op_names`` of its compiled
    text; each that ran gets its ``scope_dev_s``."""
    window_s = (span[1] - span[0]) if span and span[1] else 0.0
    dev_planes = sorted({p for p, *_ in events if DEVICE_PLANE.match(p)})
    busy, ops, programs, dev_iv, mod_ops = [], {}, {}, [], {}
    for plane in dev_planes:
        iv = [(s, s + d) for p, l, n, s, d in events
              if p == plane and l == OPS_LINE]
        if iv:
            busy.append(_union(iv))
        if plane == dev_planes[0]:
            dev_iv = iv
        mods = sorted((s, s + d, module_name(n)) for p, l, n, s, d in events
                      if p == plane and l == MODULE_LINE)
        starts = [m[0] for m in mods]
        for s0, s1, name in mods:
            m = programs.setdefault(name, {"n": 0, "dev_s": 0.0})
            m["n"] += 1
            m["dev_s"] += (s1 - s0) / 1e9
        for p, l, n, s, d in events:
            if p == plane and l == OPS_LINE:
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
                key = f"{mod}/{n.split(' = ')[0]}"
                ops[key] = ops.get(key, 0) + d
                if plane == dev_planes[0]:
                    mod_ops.setdefault(mod, []).append((s, s + d, n))
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    lines = {}
    for p, l, *_ in events:
        lines[(p, l)] = lines.get((p, l), 0) + 1
    return {"busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
            "lines": sorted(lines.items(), key=lambda kv: -kv[1])[:12],
            "window_s": window_s, "programs": programs,
            "device_ops": top(ops),
            "idle_gaps": top(_idle_by_span(events, dev_iv)),
            "scope_dev_s": {m: _scopes(mod_ops[m], names)
                            for m, names in (op_names or {}).items()
                            if m in mod_ops}}
