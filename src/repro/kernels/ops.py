"""Public jit'd wrappers around the Pallas SQS kernels.

The backend decides how a kernel runs, and nothing else does: kernels
COMPILE everywhere except on the CPU backend, where the Pallas
interpreter runs them (that is how the test suite exercises them).

The wrappers handle vocab padding (``sqs_fused.pad_vocab``, -inf logits) and
adapt kernel outputs to the ``core.sqs.SQSResult`` interface, so the engine
can swap jnp ↔ Pallas paths with one flag.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sqs import SQSResult
from repro.kernels import ref as ref_mod
from repro.kernels import sqs_fused as k


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_logits(logits):
    B, V = logits.shape
    Vp = k.pad_vocab(V)
    if Vp == V:
        return logits.astype(jnp.float32), V
    pad = jnp.full((B, Vp - V), -jnp.inf, jnp.float32)
    return jnp.concatenate([logits.astype(jnp.float32), pad], axis=-1), V


@functools.partial(jax.jit, static_argnames=("temperature", "ell",
                                             "use_ref"))
def sqs_threshold(logits, beta, temperature: float = 1.0, ell: int = 100,
                  use_ref: bool = False) -> SQSResult:
    """C-SQS edge step, fused:  softmax(T) → support {q ≥ β} → dropped
    mass → lattice counts with Σb = ℓ exact.  logits: (B, V); beta: (B,)."""
    lp, V = _pad_logits(logits)
    return _sqs(lp, V, beta.astype(jnp.float32), temperature, ell, 0,
                use_ref)


@functools.partial(jax.jit, static_argnames=("K", "temperature", "ell",
                                             "use_ref"))
def sqs_topk(logits, K: int, temperature: float = 1.0, ell: int = 100,
             use_ref: bool = False) -> SQSResult:
    """K-SQS edge step: bisection top-K threshold + fused quantizer, both
    on the kernel's own softmax."""
    lp, V = _pad_logits(logits)
    return _sqs(lp, V, None, temperature, ell, K, use_ref)


def _sqs(lp, V, beta, temperature, ell, exact_k, use_ref) -> SQSResult:
    fn = ref_mod.sqs_fused_ref if use_ref else functools.partial(
        k.sqs_fused_call, interpret=_interpret())
    b, mask, stats = fn(lp, beta, inv_temp=1.0 / max(temperature, 1e-4),
                        ell=ell, exact_k=exact_k)
    q_hat = (b[:, :V].astype(jnp.float32) / ell)
    return SQSResult(q_hat, mask[:, :V].astype(bool), stats[:, 0],
                     stats[:, 1].astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("use_ref",))
def gqa_decode(q, k, v, pos, k_scale=None, v_scale=None,
               use_ref: bool = False):
    """Flash-decode GQA attention (optional int8 KV).  Pads the cache
    sequence to the kernel block size; stale/padded slots are masked by
    ``pos``.  Returns (B, nq, hd) f32."""
    from repro.kernels import decode_attention as da
    if use_ref:
        return ref_mod.gqa_decode_ref(q, k, v, pos, k_scale, v_scale)
    B, S, nkv, hd = k.shape
    blk = min(da.S_BLOCK, max(128, S))
    pad = (-S) % blk
    if pad:
        padw = [(0, 0), (0, pad), (0, 0), (0, 0)]
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, [(0, 0), (0, pad), (0, 0)])
            v_scale = jnp.pad(v_scale, [(0, 0), (0, pad), (0, 0)])
    return da.flash_gqa_decode_call(q, k, v, pos, k_scale, v_scale,
                                    s_block=blk, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("use_ref",))
def paged_gqa_decode(q, k, v, page_table, pos, k_scale=None, v_scale=None,
                     use_ref: bool = False):
    """Paged flash-decode GQA attention: K/V live in a shared page pool
    (P, page_size, nkv, hd) addressed through per-slot ``page_table``
    (B, max_pages) int32 (every entry a valid pool row; map host FREE
    entries to the trash page first).  Returns (B, nq, hd) f32."""
    from repro.kernels import decode_attention as da
    if use_ref:
        return ref_mod.paged_gqa_decode_ref(q, k, v, page_table, pos,
                                            k_scale, v_scale)
    return da.paged_flash_gqa_decode_call(q, k, v, page_table, pos,
                                          k_scale, v_scale,
                                          interpret=_interpret())
