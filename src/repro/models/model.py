"""Top-level model API — architecture-agnostic entry points.

    init_params(cfg, key)                        -> params pytree
    train_loss(cfg, params, batch)               -> (loss, metrics)
    prefill(cfg, params, tokens, ...)            -> (last_logits, cache)
    extend_step(cfg, params, tokens, cache, pos) -> (logits (B,L,V), cache)
    decode_step(cfg, params, token, cache, pos)  -> (logits (B,V), cache)

``extend_step`` with L>1 is the speculative-decoding verification pass
(target model scores L draft tokens against its cache in parallel).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

from repro.sharding import act_sharding as _act


def set_mesh(mesh, axes, seq_parallel: bool = False):
    _act.set_mesh(mesh, axes, seq_parallel)


def _constrain(x, *spec):
    return _act.constrain(x, *spec)
from repro.models import attention as attn_mod
from repro.models import encdec as encdec_mod
from repro.models import transformer as tfm
from repro.models.layers import (compute_dtype, embed_apply, init_embed,
                                 lm_head_apply, rmsnorm, split_keys)


# ----------------------------------------------------------------------
# Init
# ----------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key):
    """Random parameters, stored in the config's compute dtype (bf16 at
    published widths, so Qwen2.5-3B's 3.1 B parameters take 6.2 GB of
    HBM, not 12.4 GB; every matmul computes in that dtype anyway).  Under
    jit the float32 draws are fused into the cast and never materialise
    as whole stacked leaves."""
    ks = split_keys(key, 5)
    params = {
        "embed": init_embed(ks[0], cfg),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    cross = cfg.n_encoder_layers > 0
    if cross:
        params["encoder"] = encdec_mod.init_encoder(ks[1], cfg)
    if cfg.n_prefix_layers:
        pks = split_keys(ks[2], cfg.n_prefix_layers)
        params["prefix"] = {
            f"l{i}": tfm.init_block(pks[i], cfg, "attn", "mlp", cross=cross)
            for i in range(cfg.n_prefix_layers)}
    params["body"] = tfm.init_body(ks[3], cfg, cross=cross)
    dt = compute_dtype(cfg)
    return jax.tree.map(
        lambda a: a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, params)


def param_count(params) -> int:
    return int(sum(x.size for x in jax.tree.leaves(params)))


# ----------------------------------------------------------------------
# Cache
# ----------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, seq: int, dtype=None,
               enc_seq: int = 0, paged=None):
    """``paged``: an ``attention.PagedSpec`` — eligible body attention
    layers get a shared page pool + per-slot page tables instead of
    dense (B, seq, ...) KV; prefix layers and non-eligible blocks keep
    their dense/stateful caches."""
    dtype = dtype or compute_dtype(cfg)
    cache = {}
    if cfg.n_prefix_layers:
        cache["prefix"] = {
            f"l{i}": tfm.init_block_cache(cfg, "attn", batch, seq, dtype)
            for i in range(cfg.n_prefix_layers)}
    cache["body"] = tfm.init_body_cache(cfg, batch, seq, dtype, paged=paged)
    if cfg.n_encoder_layers:
        N = cfg.n_periods
        kv = {"k": jnp.zeros((batch, enc_seq, cfg.n_kv_heads, cfg.head_dim),
                             dtype),
              "v": jnp.zeros((batch, enc_seq, cfg.n_kv_heads, cfg.head_dim),
                             dtype)}
        cache["cross"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (N,) + a.shape),
            {f"p{i}": kv for i in range(cfg.period)})
    return cache


def set_page_tables(cache, pt):
    """Refresh every paged body period's ``page_table`` leaf from a
    sanitized device table ``pt`` (B, maxp).  The engine calls this
    after each host-side allocator change (admit / growth / rollback
    shrink / release) so the next jitted round reads current mappings."""
    body = {}
    for name, sub in cache["body"].items():
        if isinstance(sub, dict) and "page_table" in sub:
            N = sub["page_table"].shape[0]
            sub = dict(sub)
            sub["page_table"] = jnp.broadcast_to(pt[None], (N,) + pt.shape)
        body[name] = sub
    out = dict(cache)
    out["body"] = body
    return out


def write_prefill_to_slot(cfg: ModelConfig, big, small, slot: int,
                          pt_row=None, length: int = 0):
    """Scatter a batch-1 prefill cache into a multi-slot cache.  Dense /
    stateful leaves go into batch row ``slot`` (body/cross leaves carry
    batch at axis 1, prefix at axis 0); paged body periods instead write
    the prompt's first ``length`` positions through ``pt_row`` into the
    shared page pool."""
    out = dict(big)
    for name, sub in big.items():
        if name == "body":
            nb = {}
            for pname, pcache in sub.items():
                if isinstance(pcache, dict) and "page_table" in pcache:
                    nb[pname] = attn_mod.prefill_into_pages(
                        pcache, small["body"][pname], pt_row, length)
                else:
                    nb[pname] = jax.tree.map(
                        lambda b, s: jax.lax.dynamic_update_slice_in_dim(
                            b, s.astype(b.dtype), slot, axis=1),
                        pcache, small["body"][pname])
            out[name] = nb
        else:
            axis = 0 if name == "prefix" else 1
            out[name] = jax.tree.map(
                lambda b, s, a=axis: jax.lax.dynamic_update_slice_in_dim(
                    b, s.astype(b.dtype), slot, axis=a),
                sub, small[name])
    return out


def _build_cross_kvs(cfg: ModelConfig, body_p, enc_out):
    """Precompute per-layer cross-attention K/V from encoder output."""
    def per_pos(cross_p):
        return jax.vmap(lambda cp: attn_mod.cross_kv(cfg, cp, enc_out))(
            cross_p)
    return {f"p{i}": per_pos(body_p[f"p{i}"]["cross"])
            for i in range(cfg.period)}


# ----------------------------------------------------------------------
# Shared forward plumbing
# ----------------------------------------------------------------------
def _default_positions(cfg: ModelConfig, batch: int, seq: int, start=0):
    p = jnp.arange(seq, dtype=jnp.int32)[None] + \
        (start if isinstance(start, int) else start[:, None])
    p = jnp.broadcast_to(p, (batch, seq)).astype(jnp.int32)
    if cfg.rope_type == "mrope":
        return jnp.broadcast_to(p[None], (3, batch, seq))
    return p


def _prefix_apply(cfg, params, x, *, mode, positions, caches=None, pos=None):
    new_caches = {}
    for i in range(cfg.n_prefix_layers):
        name = f"l{i}"
        ck = caches[name] if caches is not None else None
        x, _, nc, _ = tfm.apply_block(cfg, params["prefix"][name], x, "attn",
                                      "mlp", mode=mode, positions=positions,
                                      cache=ck, pos=pos)
        new_caches[name] = nc
    return x, new_caches


def _head(cfg, params, x):
    x = rmsnorm(x, params["final_norm"], cfg.rms_eps)
    return lm_head_apply(params["embed"], x, cfg.tie_embeddings)


# ----------------------------------------------------------------------
# Train
# ----------------------------------------------------------------------
def train_loss(cfg: ModelConfig, params, batch, remat: bool = True):
    """batch: {"tokens": (B, S+1) int32[, "positions": rope positions,
    "enc_embeds": (B, S_enc, d) for enc-dec, "loss_mask": (B, S)]}."""
    dt = compute_dtype(cfg)
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    B, S = inputs.shape
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, B, S)
    x = embed_apply(params["embed"], inputs, dt)
    enc_out = None
    if cfg.n_encoder_layers:
        enc_out = encdec_mod.encode(cfg, params["encoder"],
                                    batch["enc_embeds"].astype(dt))
    if cfg.n_prefix_layers:
        x, _ = _prefix_apply(cfg, params, x, mode="train",
                             positions=positions)
    x, aux, _ = tfm.apply_body(cfg, params["body"], x, mode="train",
                               positions=positions, enc_out=enc_out,
                               remat=remat)
    if _act.AXES is not None:
        x = _constrain(x, _act.AXES.dp, None, None)
    logits = _head(cfg, params, x).astype(jnp.float32)
    if _act.AXES is not None:
        # logits (B, S, V): batch over data, vocab over model — keeps the
        # 0.4 TB fp32 logits tensor fully sharded through the CE (§Perf H2)
        logits = _constrain(logits, _act.AXES.dp, None, _act.AXES.model)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    ce = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    loss = ce + aux
    acc = ((logits.argmax(-1) == labels) * mask).sum() / \
        jnp.maximum(mask.sum(), 1.0)
    return loss, {"ce": ce, "aux": aux, "accuracy": acc}


def forward_logits(cfg: ModelConfig, params, tokens, positions=None,
                   enc_embeds=None):
    """Teacher-forced logits (B, S, V) — oracle for tests and the
    recompute-style verification path."""
    dt = compute_dtype(cfg)
    B, S = tokens.shape
    if positions is None:
        positions = _default_positions(cfg, B, S)
    x = embed_apply(params["embed"], tokens, dt)
    enc_out = None
    if cfg.n_encoder_layers:
        enc_out = encdec_mod.encode(cfg, params["encoder"],
                                    enc_embeds.astype(dt))
    if cfg.n_prefix_layers:
        x, _ = _prefix_apply(cfg, params, x, mode="train",
                             positions=positions)
    # dropless MoE: the oracle must reproduce the serve path, whose
    # inference-mode routing never drops tokens (moe.moe_apply)
    x, _, _ = tfm.apply_body(cfg, params["body"], x, mode="train",
                             positions=positions, enc_out=enc_out,
                             moe_dropless=True)
    return _head(cfg, params, x).astype(jnp.float32)


# ----------------------------------------------------------------------
# Serve: prefill / extend / decode
# ----------------------------------------------------------------------
def prefill(cfg: ModelConfig, params, tokens, positions=None,
            enc_embeds=None, cache_len: Optional[int] = None):
    """Run the prompt, build the decode cache.  Returns (last_logits, cache).
    ``cache_len``: total cache capacity (>= prompt length)."""
    dt = compute_dtype(cfg)
    B, S = tokens.shape
    cache_len = cache_len or S
    if positions is None:
        positions = _default_positions(cfg, B, S)
    x = embed_apply(params["embed"], tokens, dt)
    enc_out = None
    cross_kvs = None
    if cfg.n_encoder_layers:
        enc_out = encdec_mod.encode(cfg, params["encoder"],
                                    enc_embeds.astype(dt))
        cross_kvs = _build_cross_kvs(cfg, params["body"], enc_out)
    cache = {}
    if cfg.n_prefix_layers:
        x, pc = _prefix_apply(cfg, params, x, mode="prefill",
                              positions=positions)
        cache["prefix"] = _grow_prefix_cache(cfg, pc, cache_len, dt)
    x, _, body_caches = tfm.apply_body(cfg, params["body"], x,
                                       mode="prefill", positions=positions,
                                       cross_kvs=cross_kvs)
    cache["body"] = _grow_body_cache(cfg, body_caches, cache_len, dt)
    if cross_kvs is not None:
        cache["cross"] = cross_kvs
    logits = _head(cfg, params, x[:, -1:])[:, 0].astype(jnp.float32)
    return logits, cache


def _cache_capacity(cfg, cache_len):
    if cfg.attention == "sliding" and cfg.sliding_window:
        return min(cache_len, cfg.sliding_window)
    return cache_len


def _grow_kv(cfg, kv, cache_len, dt):
    """Pad prefill KV (length S) out to cache capacity (seq axis = 1)."""
    cap = _cache_capacity(cfg, cache_len)

    def pad(a):
        if a.ndim >= 3 and a.shape[1] < cap:
            pads = [(0, 0)] * a.ndim
            pads[1] = (0, cap - a.shape[1])
            return jnp.pad(a, pads)
        return a
    return jax.tree.map(pad, kv)


def _grow_prefix_cache(cfg, pc, cache_len, dt):
    return {k: _grow_kv(cfg, v, cache_len, dt) for k, v in pc.items()}


def _grow_body_cache(cfg, bc, cache_len, dt):
    """Body caches are period-stacked: KV seq axis = 2."""
    if cfg.n_periods == 0:
        return bc
    cap = _cache_capacity(cfg, cache_len)
    out = {}
    for i in range(cfg.period):
        name = f"p{i}"
        if cfg.block_pattern[i] == "attn":
            def pad(a):
                if a.ndim >= 4 and a.shape[2] < cap:
                    pads = [(0, 0)] * a.ndim
                    pads[2] = (0, cap - a.shape[2])
                    return jnp.pad(a, pads)
                return a
            out[name] = jax.tree.map(pad, bc[name])
        else:
            out[name] = bc[name]
    return out


def extend_step(cfg: ModelConfig, params, tokens, cache, pos,
                collect_traj: bool = False):
    """tokens: (B, L) new tokens; pos: (B,) absolute index of tokens[:,0].
    Returns (logits (B, L, V) fp32, updated cache[, state_traj]).

    ``collect_traj=True`` additionally returns per-position sequential-state
    snapshots (body-stacked, seq axis = 2) for SSM/hybrid speculative-
    decoding rollback — see repro.core.engine.rollback_cache."""
    dt = compute_dtype(cfg)
    B, L = tokens.shape
    positions = _default_positions(cfg, B, L, start=pos)
    x = embed_apply(params["embed"], tokens, dt)
    new_cache = dict(cache)
    if cfg.n_prefix_layers:
        x, pc = _prefix_apply(cfg, params, x, mode="extend",
                              positions=positions, caches=cache["prefix"],
                              pos=pos)
        new_cache["prefix"] = pc
    cross_kvs = cache.get("cross")
    out = tfm.apply_body(
        cfg, params["body"], x, mode="extend", positions=positions,
        caches=cache["body"], pos=pos, cross_kvs=cross_kvs,
        collect_traj=collect_traj)
    if collect_traj:
        x, _, body_caches, trajs = out
    else:
        x, _, body_caches = out
        trajs = None
    new_cache["body"] = body_caches
    logits = _head(cfg, params, x).astype(jnp.float32)
    if collect_traj:
        return logits, new_cache, trajs
    return logits, new_cache


def decode_step(cfg: ModelConfig, params, token, cache, pos):
    """token: (B,) int32.  Returns (logits (B, V), cache)."""
    logits, cache = extend_step(cfg, params, token[:, None], cache, pos)
    return logits[:, 0], cache
