"""Committed tokens per slot-round (delivery) in the window: 1 + the
accepted drafts, the speculative-decoding gain."""


def read(rec):
    win = [d for d in rec.in_window() if d[3]]
    return sum(d[3] for d in win) / len(win) if win else None
