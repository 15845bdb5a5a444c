"""Observability layer for the serving stack.

    SpanTracer       — dual-clock (modeled + wall) span tracing with
                       Chrome-trace-event / Perfetto export (obs.trace)
    MetricsRegistry  — counters / gauges / fixed-bucket histograms with
                       deterministic snapshots (obs.metrics)
    DecompTracker    — online Theorem-1 rejection decomposition and
                       conformal coverage telemetry (obs.decomp)
    Obs              — the bundle threaded through ServeSession /
                       EventDrivenLoop / EdgeClient and on into the
                       engine (obs.handle); ``NULL_OBS`` is the shared
                       disabled instance (near-zero hot-path cost)

Load-bearing invariant (pinned by tests/test_fuzz_serve.py's obs axis
and the tcp differential tests): observability moves NO tokens — every
instrument only reads host values and the host's clock, never tokens,
RNG state or scheduling, so streams are bit-identical with obs on or
off, over the simulator and over sockets.
"""
from __future__ import annotations

from repro.obs.decomp import DecompTracker
from repro.obs.handle import NULL_OBS, Obs
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               percentile, summary_stats)
from repro.obs.trace import (CLOCK_MODELED, CLOCK_WALL, SpanTracer,
                             span_names_by_clock)

__all__ = [
    "CLOCK_MODELED", "CLOCK_WALL", "Counter", "DecompTracker", "Gauge",
    "Histogram", "MetricsRegistry", "NULL_OBS", "Obs", "SpanTracer",
    "percentile", "snapshot_topology", "span_names_by_clock",
    "summary_stats",
]


def snapshot_topology(metrics: MetricsRegistry, topo) -> None:
    """Fold a ``serve.cells.CellTopology``'s end-of-run link and
    scheduler state into the registry: per-cell uplink/downlink traffic
    + backlog, and per-cell admission/preemption counts."""
    if not metrics.enabled:
        return
    for cell in topo.cells:
        base = f"serve.cell{cell.cell_id}"
        for lname, link in (("uplink", cell.uplink),
                            ("downlink", cell.downlink)):
            metrics.counter(f"{base}.{lname}.msgs").inc(link.n_msgs)
            metrics.counter(f"{base}.{lname}.delayed_msgs").inc(
                link.n_delayed)
            metrics.gauge(f"{base}.{lname}.bits_total").set(
                link.bits_total)
            metrics.gauge(f"{base}.{lname}.peak_backlog_s").set(
                link.peak_backlog_s)
        sched = cell.sched
        metrics.counter(f"{base}.sched.submitted").inc(sched.n_submitted)
        metrics.counter(f"{base}.sched.admitted").inc(sched.n_admitted)
        metrics.counter(f"{base}.sched.rejected").inc(len(sched.rejected))
        metrics.counter(f"{base}.sched.preemptions").inc(
            sched.n_preemptions)
        metrics.gauge(f"{base}.sched.queue_depth").set(len(sched.waiting))
