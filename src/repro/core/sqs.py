"""SQS sparsifiers: K-SQS (fixed top-K) and C-SQS (conformal threshold).

Given the edge SLM distribution q (B, V):
  1. select support X  (top-K rule, eq. (5) regime — or threshold rule,
     eq. (6):  X(β) = {x : q(x) ≥ β});
  2. renormalise onto X → q̃;
  3. lattice-quantise → q̂ (slq.lattice_quantize);
  4. the edge SAMPLES its draft token from q̂ (Quantize-and-Sample).

``sparsify_*`` return (q_hat, mask, dropped_mass, K) — everything the
conformal controller, bit accounting and verifier need.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.slq import (from_order_key, lattice_quantize,
                            nth_largest_key, order_key)


class SQSResult(NamedTuple):
    q_hat: jnp.ndarray        # (B, V) quantized sparse distribution
    mask: jnp.ndarray         # (B, V) support set X
    dropped: jnp.ndarray      # (B,) α_n(X): mass outside the support
    K: jnp.ndarray            # (B,) support cardinality


def softmax_temp(logits, temperature: float):
    t = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-4)
    return jax.nn.softmax(logits.astype(jnp.float32) / t, axis=-1)


def _renormalize(q, mask):
    qm = jnp.where(mask, q, 0.0)
    s = qm.sum(-1, keepdims=True)
    return qm / jnp.maximum(s, 1e-30)


def sparsify_topk(q, K: int, ell: int) -> SQSResult:
    """K-SQS: keep the K largest-probability tokens (fixed K)."""
    V = q.shape[-1]
    K = min(K, V)
    n = jnp.full(q.shape[:-1] + (1,), K, jnp.int32)
    kth = from_order_key(nth_largest_key(order_key(q), n))   # (B, 1)
    mask = q >= kth
    # ties could admit > K entries: break by index (keep first K)
    over = jnp.cumsum(mask.astype(jnp.int32), axis=-1) <= K
    mask = mask & over
    dropped = jnp.where(mask, 0.0, q).sum(-1)
    q_tilde = _renormalize(q, mask)
    q_hat, _ = lattice_quantize(q_tilde, ell, mask)
    return SQSResult(q_hat, mask, dropped,
                     mask.sum(-1).astype(jnp.int32))


def sparsify_threshold(q, beta, ell: int) -> SQSResult:
    """C-SQS support rule, eq. (6): X(β) = {x : q(x) ≥ β}.  The argmax
    token is always kept so the support is never empty."""
    beta = jnp.asarray(beta, jnp.float32)
    if beta.ndim == q.ndim - 1:
        beta = beta[..., None]
    mask = q >= beta
    top1 = jax.nn.one_hot(q.argmax(-1), q.shape[-1], dtype=jnp.bool_)
    mask = mask | top1
    dropped = jnp.where(mask, 0.0, q).sum(-1)
    q_tilde = _renormalize(q, mask)
    q_hat, _ = lattice_quantize(q_tilde, ell, mask)
    return SQSResult(q_hat, mask, dropped,
                     mask.sum(-1).astype(jnp.int32))


def dense_qs(q, ell: int) -> SQSResult:
    """Baseline [22]: quantize the FULL distribution (K = V)."""
    mask = jnp.ones_like(q, jnp.bool_)
    q_hat, _ = lattice_quantize(q, ell, mask)
    V = q.shape[-1]
    return SQSResult(q_hat, mask, jnp.zeros(q.shape[:-1], jnp.float32),
                     jnp.full(q.shape[:-1], V, jnp.int32))


def no_compression(q) -> SQSResult:
    """Baseline: uncompressed uplink (q̂ = q)."""
    mask = jnp.ones_like(q, jnp.bool_)
    V = q.shape[-1]
    return SQSResult(q.astype(jnp.float32), mask,
                     jnp.zeros(q.shape[:-1], jnp.float32),
                     jnp.full(q.shape[:-1], V, jnp.int32))
