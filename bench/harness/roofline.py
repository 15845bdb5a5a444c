"""Roofline shares and whole-window FLOPs from the run's records, each
model's kind's counts (harness.counts states the model) and the trace
reduction of harness.tracefile."""
from __future__ import annotations

from harness import counts

_CALL = {"draft": "draft_call", "spec": "draft_call",
         "verify": "verify_call"}


def _count(rec, call):
    """(flops, bytes) of one recorded engine call, by its model's kind."""
    m = rec.target if call[0] == "verify" else rec.draft
    return getattr(m.kind, _CALL[call[0]])(m.cfg, call[5], rec.L)


def share(rec, kinds, module: str):
    """Mean least time per call over mean device time per program run,
    in %.  None when the trace or the program's events are missing."""
    t = rec.trace
    if not t or rec.peaks is None:
        return None
    prog = t["programs"].get(module)
    calls = [c for c in rec.calls if c[0] in kinds]     # the traced window
    if not prog or not prog["n"] or not calls:
        return None
    least = [counts.least_time(*_count(rec, c), rec.peaks)[0] for c in calls]
    return (sum(least) / len(least)) / (prog["dev_s"] / prog["n"]) * 100.0


def window_flops(rec) -> float:
    f = sum(_count(rec, c)[0] for c in rec.calls)
    for _, S in rec.admissions:
        for m in (rec.target, rec.draft):
            f += m.kind.prefill_call(m.cfg, S)[0]
    return float(f)
