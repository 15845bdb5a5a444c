"""Share of the window's wall time outside the program's timed step
calls (draft, speculative draft, verify): codec, packing, q-hat
handoff, admission prefill, scheduling."""


def read(rec):
    timed = sum(c[3] for c in rec.calls)
    return (1.0 - timed / rec.window_s) * 100.0 if rec.calls else None
