"""95th percentile of the serving-clock gap between a request's
deliveries in the window: the program's modeled deployment time, the
measured t_slm and t_llm plus the modeled radio links (first deliveries
have no gap and are left out)."""
import numpy as np


def read(rec):
    g = [gap for gap, _ in rec.gaps("serve")]
    return float(np.percentile(g, 95)) * 1e3 if g else None
