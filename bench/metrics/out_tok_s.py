"""Committed output tokens delivered in the window per wall second.
Counts all host and device work, including what the serving clock
leaves out (admission prefill, codec, packing, the q-hat handoff)."""


def read(rec):
    n = sum(d[3] for d in rec.in_window())
    return n / rec.window_s if n else None
