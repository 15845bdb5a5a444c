"""Mean cloud verify step time (the program's own t_llm) of the
window's verify calls."""


def read(rec):
    t = [c[3] for c in rec.calls if c[0] == "verify"]
    return sum(t) / len(t) * 1e3 if t else None
