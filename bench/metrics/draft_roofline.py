"""Least time of the draft calls in the traced window over the device
time of the draft step program (XLA module ``jit__draft_round``)."""
from harness import roofline


def read(rec):
    return roofline.share(rec, ("draft", "spec"), "jit__draft_round")
