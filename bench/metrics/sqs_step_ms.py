"""SQS device time per run of the draft step program, ms: the leaf ops
of ``jit__draft_round`` under ``named_scope("sqs")`` in the trace (the
scope read from the program's compiled text), over its runs there."""
from harness import progspans


def read(rec):
    if not rec.scope_dev_s or not rec.trace:
        return None
    return progspans.sqs_step_ms(rec.scope_dev_s.get("jit__draft_round"),
                                 rec.trace["programs"])
