"""Plain float32 reference of the served dense models.

A straightforward transcription of the published architecture (Qwen2 /
LLaMA): pre-RMSNorm, rotary attention with the rotate-half convention,
optional QKV bias, causal softmax attention with grouped KV heads,
SwiGLU MLP, final RMSNorm and a tied or untied head.  No cache, no
batching, no kernels: one teacher-forced pass over a whole sequence,
every matmul at ``highest`` precision.  It imports nothing of the
program; it reads the benchmark's semantic weights (harness.weights).

Memory: the layers run in a ``lax.scan`` that upcasts one layer's bf16
weights at a time, queries are taken in chunks, and the head is applied
only to the positions asked for.  Sequences are padded at the end to a
multiple of ``PAD`` so that one compiled program serves many lengths
(causal masking makes the padding invisible to earlier positions).

``quant`` lowers the weights' precision for the control run: "int8"
(per-output-channel absmax) or "fp8" (float8_e4m3fn, per-channel scale).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD = 256
Q_CHUNK = 512


def _quant(w, mode, axis):
    """Round ``w`` (f32) through a lower precision along reduction
    ``axis`` (the input dimension), keeping a scale per output channel."""
    if mode is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    if mode == "int8":
        s = jnp.maximum(amax, 1e-12) / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if mode == "fp8":
        s = jnp.maximum(amax, 1e-12) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(mode)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, H, hd) — rotate-half RoPE at absolute positions pos (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(spec, x, lw, quant):
    eps, theta, nq, nkv, hd = spec
    f32 = lambda name, axis: _quant(lw[name].astype(jnp.float32), quant,
                                    axis)
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rmsnorm(x, lw["norm1"].astype(jnp.float32), eps)
    q = jnp.einsum("sd,dnh->snh", h, f32("wq", 0))
    k = jnp.einsum("sd,dnh->snh", h, f32("wk", 0))
    v = jnp.einsum("sd,dnh->snh", h, f32("wv", 0))
    if "bq" in lw:
        q = q + lw["bq"].astype(jnp.float32)
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    group = nq // nkv
    k = jnp.repeat(k, group, axis=1)                       # (S, nq, hd)
    v = jnp.repeat(v, group, axis=1)
    outs = []
    for c0 in range(0, S, Q_CHUNK):
        qc = q[c0:c0 + Q_CHUNK]
        s = jnp.einsum("cnh,snh->ncs", qc, k) / np.sqrt(hd)
        causal = (c0 + jnp.arange(qc.shape[0]))[:, None] >= pos[None, :]
        s = jnp.where(causal[None], s, -jnp.inf)
        outs.append(jnp.einsum("ncs,snh->cnh", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(outs, 0)
    x = x + jnp.einsum("snh,nhd->sd", o, f32("wo", (0, 1)))
    h = _rmsnorm(x, lw["norm2"].astype(jnp.float32), eps)
    g = h @ f32("w_gate", 0)
    u = h @ f32("w_up", 0)
    return x + (jax.nn.silu(g) * u) @ f32("w_down", 0)


LAYER_KEYS = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_gate", "w_up",
              "w_down", "bq", "bk", "bv")


@functools.partial(jax.jit, static_argnums=(0, 4))
def _forward(spec, w, tokens, rows, quant):
    eps = spec[0]
    x = w["embed"][tokens].astype(jnp.float32)
    layers = {k: w[k] for k in LAYER_KEYS if k in w}

    def body(x, lw):
        return _layer(spec, x, lw, quant), None

    x, _ = jax.lax.scan(body, x, layers)
    h = _rmsnorm(x[rows], w["final_norm"].astype(jnp.float32), eps)
    if "head" in w:
        head = _quant(w["head"].astype(jnp.float32), quant, 0)
    else:
        head = _quant(w["embed"].astype(jnp.float32), quant, 1).T
    return h @ head


def logits(cfg, w: dict, tokens, rows, quant=None) -> np.ndarray:
    """float32 logits (len(rows), V) of the teacher-forced sequence
    ``tokens`` at positions ``rows``."""
    tokens = np.asarray(tokens, np.int32)
    S = len(tokens)
    padded = np.zeros((-(-S // PAD) * PAD,), np.int32)
    padded[:S] = tokens
    spec = (cfg.rms_eps, cfg.rope_theta, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim)
    with jax.default_matmul_precision("highest"):
        out = _forward(spec, w, jnp.asarray(padded),
                       jnp.asarray(np.asarray(rows, np.int32)), quant)
    return np.asarray(out)
