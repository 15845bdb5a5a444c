"""Mean edge draft step time (the program's own t_slm: host clock
around the jitted SLM scan + SQS, block_until_ready) of the window's
draft calls."""


def read(rec):
    t = [c[3] for c in rec.calls if c[0] == "draft"]
    return sum(t) / len(t) * 1e3 if t else None
