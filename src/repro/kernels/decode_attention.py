"""Flash-decode GQA kernels (Pallas TPU) with optional int8 KV cache.

Decode attention is HBM-bound: one token's queries stream the whole KV
cache.  These kernels tile the cache sequence into VMEM blocks with online
-softmax accumulators (flash), grouped-query layout (the qpk query heads
of one KV head share a program), and — the beyond-paper lever for a
quantization paper — int8 KV with per-(position, head) scales dequantised
in VMEM, halving cache HBM traffic and capacity.

Two cache layouts share the kernel body:

  dense  ``flash_gqa_decode_call``: k/v (B, S, nkv, hd), grid
         (B, nkv, S_blocks) streams the contiguous cache;
  paged  ``paged_flash_gqa_decode_call``: k/v live in a page pool
         (n_pages + 1, page_size, nkv, hd) shared across slots; the grid
         walks each slot's LOGICAL page list and the BlockSpec index_map
         translates logical → physical page through a scalar-prefetched
         page table (``pltpu.PrefetchScalarGridSpec``), so the DMA
         engine gathers exactly the slot's pages — the serving-scale
         layout where HBM holds sum-of-actual-lengths, not
         slots × worst-case (core.pages.PageAllocator).

    q     : (B, nq, hd)                      bf16/f32
    pos   : (B,) int32 — entries at index > pos are masked (cache slots
            beyond the current position are stale/unwritten)
    out   : (B, nq, hd) f32

TPU block layout: a K/V block is one KV head's (positions, hd) slab of
the cache viewed as (..., S, nkv·hd), so its last two dims are
(s_block or page_size, hd) — multiples of (8, 128) for hd = 128.  int8
scales ride as (..., nkv, 1, S) rows whose block is (1, s_block): they
scale score columns (k) and probability columns (v) instead of cache
rows.  ``pos`` (and the page table) are scalar-prefetched into SMEM.

``interpret`` is explicit: the Pallas interpreter is for the CPU test
backend only (``kernels.ops`` decides from the backend).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

S_BLOCK = 512
NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, *rest, s_block: int,
            quantized: bool, scale: float):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    sb = pl.program_id(2)
    n_sb = pl.num_programs(2)

    @pl.when(sb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # (qpk, hd)
    k = k_ref[0].astype(jnp.float32)                  # (BS, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (qpk, BS)
    if quantized:
        s = s * ks_ref[0, 0]                          # (1, BS) key scales
    idx = sb * s_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(idx <= pos_ref[b], s, NEG_INF)

    m_prev = m_ref[...]                               # (qpk, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                            # (qpk, BS)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
    pv = p * vs_ref[0, 0] if quantized else p         # value scales
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        pv, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(sb == n_sb - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def _scratch(qpk: int, hd: int):
    return [pltpu.VMEM((qpk, 1), jnp.float32),
            pltpu.VMEM((qpk, 1), jnp.float32),
            pltpu.VMEM((qpk, hd), jnp.float32)]


def _head_slabs(x):
    """(..., S, nkv, hd) -> (..., S, nkv * hd): head h is block column h."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def _scale_rows(s):
    """(..., S, nkv) scales -> (..., nkv, 1, S): one lane row per head."""
    return jnp.swapaxes(s, -1, -2)[..., None, :]


def flash_gqa_decode_call(q, k, v, pos, k_scale=None, v_scale=None, *,
                          s_block: int = S_BLOCK, interpret: bool):
    """q: (B, nq, hd); k/v: (B, S, nkv, hd); pos: (B,) int32.
    S must be a multiple of s_block (ops.py pads).  Returns (B, nq, hd)
    f32."""
    B, nq, hd = q.shape
    _, S, nkv, _ = k.shape
    assert S % s_block == 0, (S, s_block)
    qpk = nq // nkv
    quantized = k_scale is not None
    kernel = functools.partial(
        _kernel, s_block=s_block, quantized=quantized,
        scale=1.0 / float(hd) ** 0.5)
    kv_spec = pl.BlockSpec((1, s_block, hd), lambda b, h, s, pos: (b, s, h))
    in_specs = [
        pl.BlockSpec((1, 1, qpk, hd), lambda b, h, s, pos: (b, h, 0, 0)),
        kv_spec, kv_spec]
    args = [q.reshape(B, nkv, qpk, hd), _head_slabs(k), _head_slabs(v)]
    if quantized:
        sc_spec = pl.BlockSpec((1, 1, 1, s_block),
                               lambda b, h, s, pos: (b, h, 0, s))
        in_specs += [sc_spec, sc_spec]
        args += [_scale_rows(k_scale), _scale_rows(v_scale)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,               # pos
            grid=(B, nkv, S // s_block),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, qpk, hd),
                                   lambda b, h, s, pos: (b, h, 0, 0)),
            scratch_shapes=_scratch(qpk, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, nkv, qpk, hd), jnp.float32),
        interpret=interpret,
    )(pos.astype(jnp.int32), *args)
    return out.reshape(B, nq, hd)


# ----------------------------------------------------------------------
# Paged flash decode: grid walks each slot's page list; the index_map
# translates logical page -> physical pool row via the scalar-prefetched
# page table, so only the slot's own pages are ever DMA'd.
# ----------------------------------------------------------------------
def _paged_kernel(pt_ref, pos_ref, *refs, page_size: int, quantized: bool,
                  scale: float):
    # identical flash body: program_id(2) is the LOGICAL page index, so
    # idx = page * page_size + offset is the absolute position and the
    # pos mask also kills trash-page blocks (allocated pages always
    # cover pos; anything mapped to trash starts beyond it).
    _kernel(pos_ref, *refs, s_block=page_size, quantized=quantized,
            scale=scale)


def paged_flash_gqa_decode_call(q, k, v, page_table, pos,
                                k_scale=None, v_scale=None, *,
                                interpret: bool):
    """q: (B, nq, hd); k/v: page pools (P, page_size, nkv, hd) where row
    P-1 may be a trash page; page_table: (B, max_pages) int32, every
    entry a valid pool row (host FREE entries pre-mapped to trash —
    models.attention.sanitize_page_table); pos: (B,) int32.  Returns
    (B, nq, hd) f32, numerically the flash equivalent of gathering the
    slot's pages into a dense cache and calling the dense kernel."""
    B, nq, hd = q.shape
    P, ps, nkv, _ = k.shape
    maxp = page_table.shape[1]
    qpk = nq // nkv
    quantized = k_scale is not None
    kernel = functools.partial(
        _paged_kernel, page_size=ps, quantized=quantized,
        scale=1.0 / float(hd) ** 0.5)
    kv_spec = pl.BlockSpec((1, ps, hd),
                           lambda b, h, i, pt, pos_r: (pt[b, i], 0, h))
    in_specs = [
        pl.BlockSpec((1, 1, qpk, hd),
                     lambda b, h, i, pt, pos_r: (b, h, 0, 0)),
        kv_spec, kv_spec]
    args = [q.reshape(B, nkv, qpk, hd), _head_slabs(k), _head_slabs(v)]
    if quantized:
        sc_spec = pl.BlockSpec((1, 1, 1, ps),
                               lambda b, h, i, pt, pos_r: (pt[b, i], h, 0, 0))
        in_specs += [sc_spec, sc_spec]
        args += [_scale_rows(k_scale), _scale_rows(v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,               # page_table, pos
        grid=(B, nkv, maxp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, qpk, hd),
                               lambda b, h, i, pt, pos_r: (b, h, 0, 0)),
        scratch_shapes=_scratch(qpk, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, qpk, hd), jnp.float32),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pos.astype(jnp.int32), *args)
    return out.reshape(B, nq, hd)


# ----------------------------------------------------------------------
# int8 KV quantization helpers (per position × head absmax)
# ----------------------------------------------------------------------
def quantize_kv(x):
    """x: (B, S, nkv, hd) -> (int8 values, f32 scales (B, S, nkv))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)
