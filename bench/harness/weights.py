"""Random weights made by the benchmark, from the seed, on the device.

The benchmark owns the weights: one jitted call draws every tensor of a
dense llama-style model (RMSNorm, RoPE attention with optional QKV bias,
SwiGLU MLP, tied or untied head) in the served dtype.  The plain
reference reads this semantic layout; ``to_program`` re-arranges the
same arrays (no copy) into the program's parameter tree.  That function
is the one place that knows the program's layout, and it checks the
result against ``jax.eval_shape`` of the program's own ``init_params``.

Scales follow the program's init (std 1/sqrt(fan_in)), so logits are
close to N(0, 1) per token.  Norm gains and QKV biases are drawn away
from 1 and 0 so that the reference checks them too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


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
            f"bench/harness/weights.py maps to: {want} vs {got}")
    return tree
