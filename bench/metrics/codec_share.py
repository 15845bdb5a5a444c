"""The wire codec's share of the traced window's wall time, %: the
program's ``edge.pack``, ``cloud.unpack``, ``verdict.pack`` and
``verdict.unpack`` spans that start in the window, over its length."""
from harness import progspans


def read(rec):
    return progspans.codec_share(rec.spans, rec.window_s)
