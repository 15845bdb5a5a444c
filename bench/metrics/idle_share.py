"""Share of the traced window with no operation running on the device
(profiler trace)."""


def read(rec):
    t = rec.trace
    if not t or t["window_s"] <= 0:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
