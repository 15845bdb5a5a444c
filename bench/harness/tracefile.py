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
              the innermost ``bench.*`` annotation (or the host's
              other work) covering each gap's midpoint, summed by name,
              the 10 largest.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


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


def reduce(events, span) -> dict:
    """``span``: (t_start, t_stop) of the traced window on the host's
    perf_counter clock (only its length is used)."""
    window_s = (span[1] - span[0]) if span and span[1] else 0.0
    dev_planes = sorted({p for p, *_ in events if DEVICE_PLANE.match(p)})
    busy, ops, programs, dev_iv = [], {}, {}, []
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
    host = [(s, s + d, n) for p, l, n, s, d in events
            if not DEVICE_PLANE.match(p) and n.startswith("bench.")]
    idle = {}
    for g0, g1 in _gaps(dev_iv):
        mid = (g0 + g1) / 2
        cover = [h for h in host if h[0] <= mid <= h[1]]
        name = min(cover, key=lambda h: h[1] - h[0])[2] if cover \
            else "host (serving loop, codec, scheduling)"
        idle[name] = idle.get(name, 0) + (g1 - g0)
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    lines = {}
    for p, l, *_ in events:
        lines[(p, l)] = lines.get((p, l), 0) + 1
    return {"busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
            "lines": sorted(lines.items(), key=lambda kv: -kv[1])[:12],
            "window_s": window_s, "programs": programs,
            "device_ops": top(ops), "idle_gaps": top(idle)}
