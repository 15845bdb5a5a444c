"""FLOPs that the window's draft, verify and prefill calls need by
their shapes, over the window's wall time times the chip's bf16 peak."""
from harness import roofline


def read(rec):
    if rec.peaks is None or not rec.calls:
        return None
    return roofline.window_flops(rec) / (rec.window_s
                                         * rec.peaks["bf16_flops_s"]) * 100
