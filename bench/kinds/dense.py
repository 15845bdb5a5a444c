"""The dense kind: llama-style decoder models (Qwen2, LLaMA).

RMSNorm, rotary attention with grouped KV heads and optional QKV bias,
SwiGLU MLP, final RMSNorm, tied or untied head.  A configuration names
this kind with ``"kind": "dense"``; the harness reaches it through
``harness.kinds`` and nothing else.  It has three parts:

* weights: one jitted call draws every tensor in the served dtype from
  the seed, on the device (``shapes``, ``make``); ``to_program``
  re-arranges the same arrays (no copy) into the program's parameter
  tree, checked against ``jax.eval_shape`` of its own ``init_params``.
  Scales follow the program's init (std 1/sqrt(fan_in)), so logits are
  close to N(0, 1) per token.  Norm gains and QKV biases are drawn away
  from 1 and 0 so that the reference checks them too;
* the plain float32 reference (``logits``, ``head``): a straightforward
  transcription of the published architecture, imports nothing of the
  program and reads only these semantic weights;
* counts by shapes (``weight_bytes``, ``kv_bytes_per_token``,
  ``verify_call``, ``draft_call``, ``prefill_call``) for the roofline
  shares and the whole-window FLOPs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


# -- weights ------------------------------------------------------------------
def shapes(cfg) -> dict:
    d, nq, nkv, hd, ff, V, N = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                cfg.head_dim, cfg.d_ff, cfg.vocab,
                                cfg.n_layers)
    s = {"embed": (V, d), "final_norm": (d,),
         "norm1": (N, d), "wq": (N, d, nq, hd), "wk": (N, d, nkv, hd),
         "wv": (N, d, nkv, hd), "wo": (N, nq, hd, d), "norm2": (N, d),
         "w_gate": (N, d, ff), "w_up": (N, d, ff), "w_down": (N, ff, d)}
    if not cfg.tie_embeddings:
        s["head"] = (d, V)
    if cfg.qkv_bias:
        s.update(bq=(N, nq, hd), bk=(N, nkv, hd), bv=(N, nkv, hd))
    return s


def _std(name: str, shape, cfg) -> float:
    if name in ("wo",):
        return (cfg.n_heads * cfg.head_dim) ** -0.5
    if name == "w_down":
        return cfg.d_ff ** -0.5
    return cfg.d_model ** -0.5


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(cfg, key, dtype):
    out = {}
    for i, (name, shp) in enumerate(sorted(shapes(cfg).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shp, jnp.float32)
        if name in ("final_norm", "norm1", "norm2"):
            w = 1.0 + 0.1 * z
        elif name in ("bq", "bk", "bv"):
            w = 0.1 * z
        else:
            w = z * _std(name, shp, cfg)
        out[name] = w.astype(dtype)
    return out


def make(cfg, seed: int) -> dict:
    """Semantic weights of ``cfg`` from ``seed`` (a 31-bit int)."""
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    return _make(cfg, jax.random.PRNGKey(seed), dtype)


def to_program(cfg, w: dict) -> dict:
    """The program's parameter tree over the same arrays; raises if the
    program's layout is not the one this adapter knows."""
    attn = {"w_q": w["wq"], "w_k": w["wk"], "w_v": w["wv"], "w_o": w["wo"]}
    if cfg.qkv_bias:
        attn.update(b_q=w["bq"], b_k=w["bk"], b_v=w["bv"])
    embed = {"embedding": w["embed"]}
    if not cfg.tie_embeddings:
        embed["lm_head"] = w["head"]
    tree = {"embed": embed, "final_norm": w["final_norm"],
            "body": {"p0": {"norm1": w["norm1"], "attn": attn,
                            "norm2": w["norm2"],
                            "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                                    "w_down": w["w_down"]}}}}
    from repro.models import init_params
    want = jax.eval_shape(init_params, cfg, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError(
            f"the program's parameter layout for {cfg.name} is not the one "
            f"bench/kinds/dense.py maps to: {want} vs {got}")
    return tree


# -- the plain reference ------------------------------------------------------
# No cache, no batching, no kernels: one teacher-forced pass over a whole
# sequence, every matmul at ``highest`` precision.  The layers run in a
# ``lax.scan`` that upcasts one layer's bf16 weights at a time, queries
# are taken in chunks, and the head is applied only to the positions
# asked for.  Sequences are padded at the end to a multiple of ``PAD`` so
# that one compiled program serves many lengths (causal masking makes
# the padding invisible to earlier positions).  ``quant`` lowers the
# weights' precision for the control run: "int8" (per-output-channel
# absmax) or "fp8" (float8_e4m3fn, per-channel scale).

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


def head(cfg, w: dict):
    """The head as a (V, d) matrix: logits = head @ final hidden state."""
    return w["head"].T if "head" in w else w["embed"]


# -- counts by shapes -----------------------------------------------------------
# The least work a call could do (harness.counts states the model).
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
