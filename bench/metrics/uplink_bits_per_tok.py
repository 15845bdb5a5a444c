"""Packed uplink bits charged in the window, framing included (the
program's SharedLink ``bits_total`` at open and close), per committed
token delivered in the window."""


def read(rec):
    n = sum(d[3] for d in rec.in_window())
    bits = rec.link_close[0] - rec.link_open[0]
    return bits / n if n else None
