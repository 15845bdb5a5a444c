"""Operations and bytes that the algorithm needs, by shapes.

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

F32 = 4


def _itemsize(cfg) -> int:
    return 2 if cfg.dtype == "bfloat16" else 4


def layer_matmul_params(cfg) -> int:
    d, nq, nkv, hd, ff = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim, cfg.d_ff)
    return d * nq * hd * 2 + d * nkv * hd * 2 + 3 * d * ff


def matmul_params_per_token(cfg) -> int:
    """Parameters a token multiplies through: every layer and the head."""
    return cfg.n_layers * layer_matmul_params(cfg) + cfg.d_model * cfg.vocab


def weight_bytes(cfg) -> int:
    """Bytes a full pass reads: layers (with norms and biases), the head,
    and — when the head is untied — no full embedding read (a gather)."""
    d, nq, nkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    per_layer = layer_matmul_params(cfg) + 2 * d
    if cfg.qkv_bias:
        per_layer += (nq + 2 * nkv) * hd
    n = cfg.n_layers * per_layer + d + d * cfg.vocab
    return n * _itemsize(cfg)


def kv_bytes_per_token(cfg) -> int:
    return cfg.n_layers * 2 * cfg.n_kv_heads * cfg.head_dim * _itemsize(cfg)


def _attn_flops(cfg, ctx: int, n_new: int) -> int:
    """Causal attention of n_new queries appended after ctx positions."""
    keys = sum(ctx + i + 1 for i in range(n_new))
    return cfg.n_layers * 4 * cfg.n_heads * cfg.head_dim * keys


def verify_call(cfg, ctxs, L: int):
    """(flops, bytes) of one verify call committing rows with contexts
    ``ctxs`` (tokens before the round's first position)."""
    T = L + 1
    flops = sum(2 * matmul_params_per_token(cfg) * T
                + _attn_flops(cfg, c, T) for c in ctxs)
    kvb = kv_bytes_per_token(cfg)
    nbytes = (weight_bytes(cfg) + sum(c * kvb for c in ctxs)
              + len(ctxs) * T * kvb + len(ctxs) * T * cfg.vocab * F32)
    return flops, nbytes


def draft_call(cfg, ctxs, L: int):
    T = L + 1
    flops = sum(2 * matmul_params_per_token(cfg) * T
                + _attn_flops(cfg, c, T) for c in ctxs)
    kvb = kv_bytes_per_token(cfg)
    nbytes = (T * weight_bytes(cfg)
              + sum((c + i) * kvb for c in ctxs for i in range(T))
              + len(ctxs) * T * kvb
              + len(ctxs) * T * cfg.vocab * F32 * 2)
    return flops, nbytes


def prefill_call(cfg, S: int):
    """Prefill of an S-token prompt (the program prefills S-1 positions
    and projects only the last one to the vocabulary)."""
    n = S - 1
    flops = (2 * cfg.n_layers * layer_matmul_params(cfg) * n
             + 2 * cfg.d_model * cfg.vocab + _attn_flops(cfg, 0, n))
    nbytes = weight_bytes(cfg) + n * kv_bytes_per_token(cfg)
    return flops, nbytes


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
