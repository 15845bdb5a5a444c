"""One cell, end to end: find its files by name, run it, read its
metrics, decide ``correct``, and print the result.

A cell ``<name>`` of BENCHMARK.json names a configuration and a traffic
mix; its files are

    bench/configs/<config>.json     sizes as run, source, cuts, draft rule
    bench/traffic/<traffic>.json    lengths, rate, burst (harness.traffic)
    bench/workloads/<name>.json     method, engine, channel, slots,
                                    audit size and the limits of `correct`
    bench/metrics/<metric>.py       one reader per metric: read(rec)

so a later cell, mix or metric is added by adding files.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load(root: str, name: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise SystemExit(f"bench/run.py: no workload {name!r} in "
                         f"BENCHMARK.json (known: {sorted(wl)})")
    w = wl[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def applies(m):
        return name in m.get("workloads", [name])

    return {"name": name, "chips": w["chips"],
            "config": _json(os.path.relpath(os.path.join(root, conf["file"]),
                                            BENCH)),
            "traffic": _json("traffic", w["traffic"] + ".json"),
            "cell": _json("workloads", name + ".json"),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def reader(metric: str):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


class Records:
    """What the metric readers read: the run's records and the cell."""

    def __init__(self, run, spec, setup_s, trace, peaks):
        self.tc, self.dc = run.tc, run.dc
        self.L = run.L
        self.cell, self.traffic = spec["cell"], spec["traffic"]
        self.t_open, self.t_close = run.t_open, run.t_close
        self.window_s = run.t_close - run.t_open
        self.now_open, self.now_close = run.now_open, run.now_close
        self.deliveries = run.deliveries
        self.calls = [c for c in run.calls if run.t_open <= c[1] < run.t_close]
        self.admissions = [a for a in run.admissions
                           if run.t_open <= a[0] < run.t_close]
        self.link_open, self.link_close = run.link_open, run.link_close
        self.setup_s = setup_s
        self.trace = trace
        self.peaks = peaks

    def in_window(self):
        return [d for d in self.deliveries
                if self.t_open <= d[2] < self.t_close]

    def gaps(self, clock: str = "serve"):
        """(gap since the same request's previous delivery, tokens) for
        every delivery in the window that has one, on the serving clock
        (``serve``, the program's modeled deployment time) or the wall
        clock (``wall``, this chip's time)."""
        i = 1 if clock == "serve" else 2
        last, out = {}, []
        for d in self.deliveries:
            rid, wall, n = d[0], d[2], d[3]
            if rid in last and self.t_open <= wall < self.t_close and n:
                out.append((d[i] - last[rid], n))
            if n:
                last[rid] = d[i]
        return out


def run(spec: dict, seed: int, seconds: float, trace: bool, t_start: float,
        devs, fault=None, quant=(), compare=True):
    """Run the cell; returns (result dict, check rows).  ``quant`` lists
    the lower weight precisions whose control is read and judged beside
    the program (bench/control.py); ``compare`` False skips the
    reference comparison (rate sweeps only)."""
    from harness import check, counts, session, tracefile
    from harness import traffic as traffic_mod
    from harness.clocks import CompileClock
    clock = CompileClock()
    cfg, cell, tr = spec["config"], spec["cell"], spec["traffic"]
    s_t, s_d = (int(x) for x in
                np.random.default_rng([seed, 1]).integers(0, 2**31 - 1, 2))
    tc, dc, tw, dw = session.build_models(cfg["model"], cfg["draft"],
                                          s_t, s_d)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    run = session.ServedRun(cell, tr, tc, dc, tw, dw, seed, seconds, clock,
                            trace_dir=trace_dir,
                            trace_seconds=cell["trace_seconds"], fault=fault)
    t_built = time.perf_counter()
    run.warm()
    t_warm = time.perf_counter()
    _memory("after warm-up", devs)
    arrivals = traffic_mod.make_trace(tr, tc.vocab, seed, tr["n_requests"])
    run.run(arrivals)
    _memory("at the close", devs)
    dev = devs[0]
    stats = dev.memory_stats() or {}
    mem_peak = int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devs)) if stats else 0
    setup_s = run.t_open - t_start
    print(f"set-up: {t_built - t_start:.3f}s to the weights and engine, "
          f"{t_warm - t_built:.3f}s warming, {run.t_open - t_warm:.3f}s "
          f"filling the slots", flush=True)
    peaks = counts.load_peaks(dev.device_kind) \
        if dev.platform == "tpu" else None
    red = None
    if trace_dir:
        red = tracefile.reduce(tracefile.load(trace_dir), run.trace_span)
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = Records(run, spec, setup_s, red, peaks)
    _diagnostics(run, rec)
    rounds = check.audit_rounds(run) if compare else []
    positions = check.audit_positions(run) if compare else {}
    attempted, failed = _attempts(run)
    # free the program's state before the reference runs
    del run
    gc.collect()
    t_ref = time.perf_counter()
    nums = check.compare(rounds, positions, tc, dc, tw, dw, cell["method"],
                         cell["engine"], quant=tuple(quant))
    rows, ok = check.verdict(nums, cell["limits"], cell["audit"])
    print(f"reference comparison: {nums['rounds']} rounds and "
          f"{nums['positions']} served positions of {len(positions)} "
          f"requests in {time.perf_counter() - t_ref:.1f}s", flush=True)
    metrics = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        v = reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    result = {"correct": bool(ok), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    if quant:
        # the control in the program's place, judged by the same verdict
        result["control"] = {
            m: dict(c, correct=check.verdict(dict(nums, **c), cell["limits"],
                                             cell["audit"])[1])
            for m, c in nums["control"].items()}
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim, _ in rows}
    result["_numbers"] = nums
    return result, rows


def _attempts(run):
    """Requests that met the window: those with a delivery in it, and
    those turned away on arrival during it (withdrawals at the close
    are not failures)."""
    served = {d[0] for d in run.deliveries
              if run.t_open <= d[2] < run.t_close}
    rejected = {r.rid for r in run.session.topo.rejected
                if r.rid not in run.withdrawn
                and run.now_open <= r.t_arrival <= run.now_close}
    return len(served | rejected), len(rejected)


def _memory(when: str, devs):
    st = [d.memory_stats() or {} for d in devs]
    print(f"memory {when}: " + "; ".join(
        f"in use {s.get('bytes_in_use')}, peak {s.get('peak_bytes_in_use')}, "
        f"limit {s.get('bytes_limit')}" for s in st), flush=True)


def _diagnostics(run, rec):
    win = rec.in_window()
    n_tok = sum(d[3] for d in win)
    kinds = {}
    for c in rec.calls:
        kinds[c[0]] = kinds.get(c[0], 0) + 1
    print(f"window: {rec.window_s:.3f}s wall, serving clock "
          f"{rec.now_open:.3f}->{rec.now_close:.3f}s; {len(win)} deliveries, "
          f"{n_tok} tokens; engine calls {kinds}; admissions "
          f"{len(rec.admissions)}", flush=True)
    print(f"compiles inside the window: {run.compiles_window}", flush=True)
    fin = [d for d in win if d[4]]
    arr = [r for r in run.trace
           if rec.now_open <= r.t_arrival < rec.now_close]
    print(f"queue: {run.queue_open} waiting at open, {run.queue_close} at "
          f"close; {len(arr)} arrivals and {len(fin)} completions in the "
          f"window's {rec.now_close - rec.now_open:.3f} serving seconds",
          flush=True)
    print("arrivals are scheduled on the serving clock, so the generator "
          "cannot run late (no lateness figure applies)", flush=True)
    for kind in ("draft", "spec", "verify"):
        c = [x for x in rec.calls if x[0] == kind]
        if c:
            print(f"  {kind}: {len(c)} calls, mean wall "
                  f"{np.mean([x[2] - x[1] for x in c]) * 1e3:.2f} ms, mean "
                  f"timed step {np.mean([x[3] for x in c]) * 1e3:.2f} ms, "
                  f"mean slots {np.mean([len(x[4]) for x in c]):.2f}",
                  flush=True)
    pb = [b for b in run.payload_bits if rec.t_open <= b[0] < rec.t_close]
    if pb:
        print(f"  uplink payloads: {sum(b[2] for b in pb)}, mean "
              f"{sum(b[1] for b in pb) / sum(b[2] for b in pb):.0f} bits "
              f"(packed, without framing)", flush=True)
    if rec.trace:
        print(f"trace lines: {rec.trace['lines']}", flush=True)
        print(f"trace programs: {rec.trace['programs']}", flush=True)
    ks = [np.count_nonzero(np.asarray(r["draft"][1]), axis=-1).tolist()
          for r in run.audit if r is not None]
    print(f"audited q-hat supports (b > 0) per position: {ks}", flush=True)


def emit(result: dict, rows):
    nums = result.pop("_numbers")
    print(f"numbers: {json.dumps(nums, default=float)}", flush=True)
    for name, v, lim, ok in rows:
        print(f"check {name} {v!r} limit {lim!r} {'ok' if ok else 'FAIL'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result, default=float), flush=True)
