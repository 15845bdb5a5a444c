"""Roofline shares and whole-window FLOPs from the run's records, the
counts of harness.counts and the trace reduction of harness.tracefile."""
from __future__ import annotations

from harness import counts

_CALL = {"draft": counts.draft_call, "spec": counts.draft_call,
         "verify": counts.verify_call}


def _cfg(rec, kind):
    return rec.tc if kind == "verify" else rec.dc


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
    least = [counts.least_time(*_CALL[c[0]](_cfg(rec, c[0]), c[5], rec.L),
                               rec.peaks)[0] for c in calls]
    return (sum(least) / len(least)) / (prog["dev_s"] / prog["n"]) * 100.0


def window_flops(rec) -> float:
    f = sum(_CALL[c[0]](_cfg(rec, c[0]), c[5], rec.L)[0] for c in rec.calls)
    for _, S in rec.admissions:
        f += counts.prefill_call(rec.tc, S)[0] + counts.prefill_call(rec.dc,
                                                                      S)[0]
    return float(f)
