"""Sparse Lattice-based Quantization (paper Appendix A.1, Algorithm 2).

Maps a (sparsified, renormalised) probability vector onto the resolution-ℓ
lattice inside the probability simplex:  q̂[i] = b[i]/ℓ with Σ b[i] = ℓ,
b[i] non-negative integers.  Rounding is nearest-integer followed by the
ζ-ranked exact-sum correction of Algorithm 2 lines 8–16.

The correction moves |δ| = |Σb' − ℓ| entries by one, and nearest rounding
bounds |δ| ≤ ℓ (b' ≥ 0, and every b'[i] > 0 has ℓq[i] ≥ ½, so b'[i] ≤
2ℓq[i]).  So it needs a selection of the first |δ| entries in ζ order, not
a ranking of the row: ``select_largest`` finds the |δ|-th key by a
fixed-pass bisection on the bits of an order-preserving uint32 key, then
cuts ties at that key by a bisection over the index (earliest index
first) — compares and row sums only, no sort, no data-dependent loop.
``sqs.sparsify_topk`` takes its K-th largest probability from the same
bisection.

Guarantee used by Theorem 1:  TV(q̃, q̂) ≤ K/(4ℓ).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_SIGN = np.uint32(0x80000000)


def order_key(x):
    """uint32 image of float32 ``x`` in the same order (−0.0 as +0.0).
    Key 0 is the image of no finite float: callers use it for "none"."""
    x = jnp.where(x == 0, 0.0, x.astype(jnp.float32))
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >= _SIGN, ~u, u | _SIGN)


def from_order_key(k):
    """Inverse of ``order_key``."""
    u = jnp.where(k >= _SIGN, k ^ _SIGN, ~k)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _count(flags):
    return flags.sum(-1, keepdims=True, dtype=jnp.int32)


# jitted, so that eager callers trace the loops once per shape
@jax.jit
def nth_largest_key(key, n):
    """Per row, the n-th largest entry of ``key`` (uint32, (..., V)):
    the largest t with count(key ≥ t) ≥ n, built bit by bit from the top
    in 32 passes.  n: (..., 1) int32 ≥ 1 and at most the row's count of
    nonzero keys; then t is a value of the row."""
    t = jnp.zeros(n.shape, jnp.uint32)

    def body(i, t):
        cand = t | jnp.left_shift(jnp.uint32(1), (31 - i).astype(jnp.uint32))
        return jnp.where(_count(key >= cand) >= n, cand, t)

    return jax.lax.fori_loop(0, 32, body, t)


def _first_n(flags, n):
    """The ``n`` lowest-index True entries of each row of ``flags`` (all of
    them if fewer): the smallest cut c with count(flags & idx < c) ≥ n, by
    bisection over c in ⌈log2 V⌉ passes."""
    V = flags.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, flags.shape, flags.ndim - 1)
    lo = jnp.zeros(n.shape, jnp.int32)          # count(idx < lo) < n
    hi = jnp.full(n.shape, V, jnp.int32)

    def body(_, c):
        lo, hi = c
        mid = jnp.right_shift(lo + hi, 1)
        ok = _count(flags & (idx < mid)) >= n
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    _, hi = jax.lax.fori_loop(0, max(1, (V - 1).bit_length()), body,
                              (lo, hi))
    return flags & (idx < hi) & (n > 0)


@jax.jit
def select_largest(key, n):
    """Mask of the first ``n`` entries of each row in (key descending,
    index ascending) order, what a stable descending sort would put first.
    Entries with key 0 are never selected; n: (..., 1) int32 ≥ 0."""
    t = nth_largest_key(key, n)
    above = key > t
    ties = (key == t) & (key > 0)
    return above | _first_n(ties, n - _count(above))


def lattice_quantize(q_tilde, ell: int, mask=None):
    """Algorithm 2 (lines 5-17), batched over leading axes.

    q_tilde: (..., V) renormalised sparse distribution (zero off-support).
    mask:    (..., V) bool support set; default = q_tilde > 0.
    Returns (q_hat, b) with q_hat = b/ℓ, Σ b = ℓ exactly, b int32 ≥ 0.
    """
    q = q_tilde.astype(jnp.float32)
    if mask is None:
        mask = q > 0
    b = jnp.floor(ell * q + 0.5)                       # line 6
    b = jnp.where(mask, b, 0.0)
    zeta = b - ell * q                                 # line 9 (ζ = b' − ℓq)
    delta = (b.sum(-1) - ell)[..., None]               # ℓ' − ℓ

    # Correction (lines 10-15), one selection per row:
    #   δ > 0: decrement the δ entries with LARGEST ζ (only b>0, on-support)
    #   δ < 0: increment the |δ| entries with SMALLEST ζ (on-support)
    # ties: earliest index first
    key = jnp.where(delta > 0,
                    jnp.where(mask & (b > 0), order_key(zeta), 0),
                    jnp.where(mask & (delta < 0), order_key(-zeta), 0))
    sel = select_largest(key, jnp.abs(delta).astype(jnp.int32))
    b = jnp.where(sel, b - jnp.sign(delta), b)
    q_hat = b / ell
    return q_hat, b.astype(jnp.int32)


def slq_distortion_bound(K, ell):
    """Theorem 1 lattice-distortion term K/(4ℓ)."""
    return jnp.asarray(K, jnp.float32) / (4.0 * ell)


def tv_distance(p, q, axis=-1):
    return 0.5 * jnp.abs(p.astype(jnp.float32)
                         - q.astype(jnp.float32)).sum(axis)
