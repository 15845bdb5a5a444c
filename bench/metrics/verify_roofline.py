"""Least time of the verify calls in the traced window over the device
time of the verify step program (XLA module ``jit__verify_round``)."""
from harness import roofline


def read(rec):
    return roofline.share(rec, ("verify",), "jit__verify_round")
