"""Transmitted drafts per uplink payload (the budget-driven L^t): the
program's ``wire.live_drafts`` over ``wire.payloads`` over the traced
window."""
from harness import progspans


def read(rec):
    return progspans.draft_len_mean(rec.counters)
