"""95th percentile, over every delivery in the window, of the wall-clock
gap since the same request's previous delivery (a request's first
delivery has no gap and is left out).  On one chip that serves the
cloud and every edge device, this is the time a request waits for its
next tokens while the other slots' rounds, admissions and host work run."""
import numpy as np


def read(rec):
    g = [gap for gap, _ in rec.gaps("wall")]
    return float(np.percentile(g, 95)) * 1e3 if g else None
