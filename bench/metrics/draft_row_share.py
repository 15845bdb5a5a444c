"""Rows of the draft step program that some slot asked for, over the
rows it computed, %: the program's ``edge.rows_requested`` over
``edge.rows_computed`` over the traced window."""
from harness import progspans


def read(rec):
    return progspans.draft_row_share(rec.counters)
