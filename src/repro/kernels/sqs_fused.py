"""Fused SQS edge kernel (Pallas TPU).

The edge hot loop is, per draft token, a full pass over the vocabulary:
temperature softmax → threshold sparsification → dropped-mass / support
statistics → lattice rounding.  Done with stock jnp ops that is ~6 HBM
sweeps of a (B, V) tensor; on TPU a whole fp32 vocab row (V ≤ 152k →
608 KB) fits comfortably in VMEM, so this kernel streams each row
HBM→VMEM once and does everything in-core:

  grid = (B,)  — one program per batch row;
  layout       — the row, padded with -inf to Vp (a multiple of
                 8 × 128), is viewed as a (Vp/128, 128) tile
                 stack, so the block (1, Vp/128, 128) spans the array's
                 last two dims and satisfies the TPU (8, 128) block rule
                 at any batch size;
  β            — scalar-prefetched into SMEM;
  outputs      — lattice counts b, the support mask, and per-row stats
                 (dropped mass, K, Σb', max logit) in lanes 0..3 of a
                 (1, 128) row.

The exact-sum correction (Algorithm 2 lines 8–16, a ζ-ranked ±1 fix) runs
IN-KERNEL: a 40-step adjacent-float bisection over ζ picks the value cut,
and a bisection over the flat index picks how many boundary ties to keep
(earliest index first) — no sort, no prefix sum, no extra HBM traffic.
``topk_threshold`` finds the K-th largest probability by fixed-iteration
bisection on the threshold (VPU compares + reductions — the TPU-native
replacement for GPU radix-select top-K), after which K-SQS reuses the same
thresholded path: the fused kernel's K-SQS mode runs that bisection on
its own in-VMEM softmax, so the threshold and the support it selects
come from bit-identical probabilities.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
ROW_TILE = 8 * LANE             # f32 (8, 128) tile: padded rows stay aligned
BISECT_ITERS = 40


def pad_vocab(V: int) -> int:
    return -(-V // ROW_TILE) * ROW_TILE


# ----------------------------------------------------------------------
# Row helpers: every reduction runs over the last two dims, so the same
# code serves a (1, Vp) row and its (Vp/128, 128) tile view.
# ----------------------------------------------------------------------
def _rsum(x):
    return jnp.sum(jnp.sum(x, axis=-1, keepdims=True), axis=-2,
                   keepdims=True)


def _rmax(x):
    return jnp.max(jnp.max(x, axis=-1, keepdims=True), axis=-2,
                   keepdims=True)


def _flat_index(shape):
    """Row-major position of each entry within the last two dims."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 2)
            * shape[-1]
            + jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1))


def _first_n(flags, n):
    """The ``n`` lowest-index True entries of ``flags`` (all of them if
    fewer).  Bisection over the cut index c for the smallest c with
    count(flags & idx < c) >= n — what a prefix sum would give, in
    compares and reductions only.  n: (1, 1) f32."""
    size = flags.shape[-2] * flags.shape[-1]
    idx = _flat_index(flags.shape)
    f = flags.astype(jnp.float32)
    lo = jnp.zeros(n.shape, jnp.int32)          # count(idx < lo) < n
    hi = jnp.full(n.shape, size, jnp.int32)

    def body(_, c):
        lo, hi = c
        mid = jnp.right_shift(lo + hi, 1)
        ok = _rsum(jnp.where(idx < mid, f, 0.0)) >= n
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    _, hi = jax.lax.fori_loop(0, max(1, (size - 1).bit_length()), body,
                              (lo, hi))
    return flags & (idx < hi) & (n > 0)


def _select_n(v, elig, n):
    """Exact selection mask of the ``n`` largest eligible entries of v,
    ties broken earliest-index-first.  All in VMEM: 40-step threshold
    bisection converges to adjacent fp32 values, then an index
    bisection trims boundary ties.  n: (1, 1) f32 >= 0."""
    NEG = -2.0                                  # v in [-0.5, 0.5]
    vv = jnp.where(elig, v, NEG)
    lo = jnp.full_like(n, NEG)
    hi = _rmax(vv) + 1e-6

    def body(_, c):
        lo, hi = c
        mid = 0.5 * (lo + hi)
        cnt = _rsum((vv >= mid).astype(jnp.float32))
        take = cnt >= n
        return jnp.where(take, mid, lo), jnp.where(take, hi, mid)

    lo, hi = jax.lax.fori_loop(0, BISECT_ITERS, body, (lo, hi))
    sel_hi = (vv >= hi) & elig
    cnt_hi = _rsum(sel_hi.astype(jnp.float32))
    ties = (vv >= lo) & ~sel_hi & elig
    sel = sel_hi | _first_n(ties, n - cnt_hi)
    return sel & (n > 0)


def _kth_bracket(q, K: int, iters: int):
    """[lo, hi] (each (1, 1)) with count(q >= lo) >= K > count(q >= hi):
    fixed-iteration bisection on the threshold, which converges to the
    K-th largest value of q (probabilities, padding 0)."""
    hi0 = _rmax(q)
    lo0 = jnp.zeros_like(hi0)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = _rsum((q >= mid).astype(jnp.float32))
        # count >= K → τ can move up; else move down
        lo = jnp.where(cnt >= K, mid, lo)
        hi = jnp.where(cnt >= K, hi, mid)
        return lo, hi

    return jax.lax.fori_loop(0, iters, body, (lo0, hi0))


def _lane_pack(shape, values):
    """values[i] (each (1, 1)) in lane i of a zero row of ``shape``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    out = jnp.zeros(shape, jnp.float32)
    for i, v in enumerate(values):
        out = jnp.where(lane == i, v, out)
    return out


# ----------------------------------------------------------------------
# Fused softmax + threshold + lattice rounding
# ----------------------------------------------------------------------
def _sqs_kernel(beta_ref, logits_ref, b_ref, mask_ref, stats_ref, *,
                inv_temp: float, ell: int, exact_k: int):
    """One batch row, entirely in VMEM.
    beta_ref: (B,) f32 in SMEM — the row's C-SQS threshold (unused by
    K-SQS, which brackets the K-th largest probability of the SAME
    in-kernel softmax it then thresholds).  logits_ref: (1, R, 128) f32
    (padded with -inf).  b_ref: (1, R, 128) i32 lattice counts with
    Σb = ℓ EXACTLY; mask_ref: (1, R, 128) i32 support; stats_ref:
    (1, 1, 128) f32, lanes 0..3 = [dropped, K, sum_b_raw, max_logit]."""
    x = logits_ref[0] * inv_temp                      # (R, 128)
    m = _rmax(x)
    e = jnp.exp(x - m)
    s = _rsum(e)
    q = e / s                                          # softmax, padded -> 0

    if exact_k > 0:
        # K-SQS: lo == the K-th largest prob (bisection converges to the
        # exact float); trim boundary ties by index so |support| == K.
        lo, _ = _kth_bracket(q, exact_k, BISECT_ITERS)
        mask = _first_n(q >= lo, jnp.full((1, 1), exact_k, jnp.float32))
    else:
        is_max = x >= m              # always keep the argmax (never empty)
        mask = (q >= beta_ref[pl.program_id(0)]) | is_max
    qm = jnp.where(mask, q, 0.0)
    sm = _rsum(qm)                                     # retained mass
    K = _rsum(mask.astype(jnp.float32))
    dropped = 1.0 - sm

    q_tilde = qm / sm                                  # renormalise
    b = jnp.floor(ell * q_tilde + 0.5)
    b = jnp.where(mask, b, 0.0)
    sum_b = _rsum(b)

    # exact-sum correction (Algorithm 2 lines 8-16), in VMEM:
    #   δ > 0: decrement the δ largest-ζ entries (b > 0, on support);
    #   δ < 0: increment the |δ| smallest-ζ entries (on support).
    zeta = b - ell * q_tilde
    delta = sum_b - ell
    dec = _select_n(zeta, mask & (b > 0), jnp.maximum(delta, 0.0))
    inc = _select_n(-zeta, mask, jnp.maximum(-delta, 0.0))
    b = b - dec.astype(jnp.float32) + inc.astype(jnp.float32)

    b_ref[0] = b.astype(jnp.int32)
    mask_ref[0] = mask.astype(jnp.int32)
    stats_ref[0] = _lane_pack((1, LANE), [dropped, K, sum_b, m])


def _row_tiles(x, fill: float):
    """(B, V) f32 -> (B, Vp/128, 128), padding each row with ``fill``
    up to Vp = pad_vocab(V)."""
    B, V = x.shape
    Vp = pad_vocab(V)
    x = x.astype(jnp.float32)
    if Vp != V:
        x = jnp.concatenate(
            [x, jnp.full((B, Vp - V), fill, jnp.float32)], axis=-1)
    return x.reshape(B, Vp // LANE, LANE)


def sqs_fused_call(logits_padded, beta, *, inv_temp: float, ell: int,
                   exact_k: int = 0, interpret: bool):
    """logits_padded: (B, Vp) f32 (-inf padded to any width; the kernel
    pads on to pad_vocab); beta: (B,) f32 C-SQS thresholds, None for
    K-SQS (exact_k > 0).  Returns (b (B,Vp) i32, mask (B,Vp) i32,
    stats (B,4) f32)."""
    B, Vp = logits_padded.shape
    if beta is None:
        beta = jnp.zeros((B,), jnp.float32)
    x = _row_tiles(logits_padded, -jnp.inf)
    R = x.shape[1]
    kernel = functools.partial(_sqs_kernel, inv_temp=inv_temp, ell=ell,
                               exact_k=exact_k)
    row = pl.BlockSpec((1, R, LANE), lambda i, beta: (i, 0, 0))
    b, mask, stats = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[row],
            out_specs=[row, row,
                       pl.BlockSpec((1, 1, LANE),
                                    lambda i, beta: (i, 0, 0))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, R, LANE), jnp.int32),
            jax.ShapeDtypeStruct((B, R, LANE), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, LANE), jnp.float32),
        ],
        interpret=interpret,
    )(beta.astype(jnp.float32), x)
    return (b.reshape(B, -1)[:, :Vp], mask.reshape(B, -1)[:, :Vp],
            stats[:, 0, :4])


# ----------------------------------------------------------------------
# Top-K threshold by bisection (K-SQS support rule without a sort)
# ----------------------------------------------------------------------
def _topk_kernel(q_ref, tau_ref, *, K: int, iters: int):
    """One row in VMEM: find the largest τ with count(q ≥ τ) ≥ K.
    q_ref: (1, R, 128) f32 (padding = 0 ≤ any τ > 0 → never counted);
    tau_ref: (1, 1, 128) f32, lanes 0..1 = [lo, hi]."""
    lo, hi = _kth_bracket(q_ref[0], K, iters)
    tau_ref[0] = _lane_pack((1, LANE), [lo, hi])


def topk_threshold_call(q_padded, K: int, *, iters: int = BISECT_ITERS,
                        interpret: bool):
    """q_padded: (B, Vp) f32 probabilities (padding = 0).
    Returns (B, 2) = [lo, hi]: count(q >= lo) >= K, count(q >= hi) < K
    — [lo, hi] bracket the K-th largest value (the fused kernel's K-SQS
    mode runs the same bracket on its own softmax and trims ties)."""
    B, _ = q_padded.shape
    q = _row_tiles(q_padded, 0.0)
    R = q.shape[1]
    kernel = functools.partial(_topk_kernel, K=K, iters=iters)
    tau = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, R, LANE), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, LANE), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, LANE), jnp.float32),
        interpret=interpret,
    )(q)
    return tau[:, 0, :2]
