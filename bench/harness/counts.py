"""Operations and bytes by shapes: the model, the peaks and the least time.

Each kind (bench/kinds/<kind>.py) counts its own calls by this model.
These are the least work a call could do, not what today's program
does: a verify call reads the target's weights once, each committed
slot's live KV once, and writes its (rows, L+1, V) float32 target
distributions once; a draft call reads the draft's weights once per
autoregressive step (L+1 of them), each committed slot's live KV per
step, and reads and writes one V-wide float32 row per slot and position
for SQS; a prefill reads the weights once and writes the prompt's KV.
Rows are the slots the call commits (a per-slot draft commits one),
so a program that computes rows nobody asked for shows as a lower
roofline share.  FLOPs count multiply-adds twice; attention is causal.
"""
from __future__ import annotations

import json
import os


def load_peaks(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound) — the larger of compute and memory time."""
    tc = flops / peaks["bf16_flops_s"]
    tm = nbytes / peaks["hbm_bytes_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
