"""Pure-jnp oracles for the Pallas kernels (numerics mirrored op-for-op).

These are the reference implementations the per-kernel allclose tests sweep
against; they also serve as the portable fallback path on backends without
Pallas support.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _row_sum(x):
    """Row sums reduced one (Vp/128, 128) row tile at a time, the way
    the kernel reduces them — a batched reduction may add in another
    order and differ in the last bits (Vp must be a multiple of 128)."""
    B, Vp = x.shape
    t = x.reshape(B, Vp // 128, 128)
    return jax.lax.map(
        lambda r: jnp.sum(jnp.sum(r, axis=-1, keepdims=True), axis=-2),
        t)


def sqs_fused_ref(logits_padded, beta, *, inv_temp: float, ell: int,
                  exact_k: int = 0):
    """Mirror of kernels.sqs_fused._sqs_kernel over the whole batch.
    logits_padded: (B, Vp) f32 (-inf padded, Vp a multiple of 128);
    beta: (B,) f32 C-SQS thresholds (None for K-SQS, exact_k > 0).
    Returns (b (B,Vp) i32, mask (B,Vp) i32, stats (B,4) f32)."""
    x = logits_padded.astype(jnp.float32) * inv_temp
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = _row_sum(e)
    q = e / s

    if exact_k > 0:
        lo = topk_threshold_ref(q, exact_k)[:, 0:1]
        cand = q >= lo
        csum = jnp.cumsum(cand.astype(jnp.float32), axis=-1)
        mask = cand & (csum <= exact_k)
    else:
        is_max = x >= m
        mask = (q >= beta[:, None]) | is_max
    qm = jnp.where(mask, q, 0.0)
    sm = _row_sum(qm)
    K = jnp.sum(mask.astype(jnp.float32), axis=-1, keepdims=True)
    dropped = 1.0 - sm

    q_tilde = qm / sm
    b = jnp.floor(ell * q_tilde + 0.5)
    b = jnp.where(mask, b, 0.0)
    sum_b = jnp.sum(b, axis=-1, keepdims=True)

    # exact-sum correction, rank-select form (ties earliest-index-first —
    # identical semantics to the kernel's bisection+cumsum select)
    zeta = b - ell * q_tilde
    delta = sum_b - ell

    def ranks(v):
        return jnp.argsort(jnp.argsort(v, axis=-1), axis=-1)

    zeta_dec = jnp.where(mask & (b > 0), zeta, -jnp.inf)
    zeta_inc = jnp.where(mask, zeta, jnp.inf)
    dec = (ranks(-zeta_dec) < delta) & mask & (b > 0)
    inc = (ranks(zeta_inc) < -delta) & mask
    b = b - dec.astype(jnp.float32) + inc.astype(jnp.float32)

    stats = jnp.concatenate([dropped, K, sum_b, m], axis=-1)
    return b.astype(jnp.int32), mask.astype(jnp.int32), stats


def topk_threshold_ref(q_padded, K: int, iters: int = 40):
    """Mirror of kernels.sqs_fused._topk_kernel (bisection, not sort)."""
    q = q_padded.astype(jnp.float32)
    hi = jnp.max(q, axis=-1, keepdims=True)
    lo = jnp.zeros_like(hi)

    def body(_, c):
        lo, hi = c
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((q >= mid).astype(jnp.float32), -1, keepdims=True)
        take = cnt >= K
        return jnp.where(take, mid, lo), jnp.where(take, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return jnp.concatenate([lo, hi], axis=-1)


def kth_largest_ref(q, K: int):
    """Sort-based K-th largest (independent oracle for the bisection)."""
    return jax.lax.top_k(q, K)[0][..., -1]


def paged_gqa_decode_ref(q, k, v, page_table, pos, k_scale=None,
                         v_scale=None):
    """Oracle for the paged flash-decode kernel: gather each slot's
    pages into a dense (B, max_pages*page_size, nkv, hd) cache in
    position order, then run the dense oracle."""
    def gather(pool):
        g = pool[page_table]                       # (B, maxp, ps, ...)
        return g.reshape((g.shape[0], g.shape[1] * g.shape[2])
                         + g.shape[3:])

    ks = gather(k_scale) if k_scale is not None else None
    vs = gather(v_scale) if v_scale is not None else None
    return gqa_decode_ref(q, gather(k), gather(v), pos, ks, vs)


def gqa_decode_ref(q, k, v, pos, k_scale=None, v_scale=None):
    """Dense oracle for the flash-decode kernel (optionally dequantising
    int8 KV with per-(position, head) scales)."""
    B, nq, hd = q.shape
    _, S, nkv, _ = k.shape
    qpk = nq // nkv
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale[..., None]
        vf = vf * v_scale[..., None]
    qg = q.reshape(B, nkv, qpk, hd).astype(jnp.float32) / float(hd) ** 0.5
    s = jnp.einsum("bkgh,bskh->bkgs", qg, kf)
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, vf)
    return o.reshape(B, nq, hd)
