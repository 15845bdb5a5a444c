"""Serving-clock time per output token over the window: the sum of the
delivery gaps over the tokens those deliveries carried.  Moves with
tokens per round (acceptance) as well as with round time."""


def read(rec):
    g = rec.gaps("serve")
    n = sum(k for _, k in g)
    return sum(gap for gap, _ in g) / n * 1e3 if n else None
