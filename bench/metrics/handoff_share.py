"""The q-hat handoff's share of the traced window's wall time, %: the
program's ``edge.fetch`` (the edge's device->host reads) and
``cloud.upload`` (the cloud's dense q-hat upload) spans in the window."""
from harness import progspans


def read(rec):
    return progspans.handoff_share(rec.spans, rec.window_s)
