"""Mean number of slots a verify call in the window commits."""


def read(rec):
    v = [len(c[4]) for c in rec.calls if c[0] == "verify"]
    return sum(v) / len(v) if v else None
