"""Support tokens an uplink payload keeps per transmitted draft: the
program's ``wire.support_tokens`` over ``wire.live_drafts``, both
counted over the traced window."""
from harness import progspans


def read(rec):
    return progspans.support_k_mean(rec.counters)
