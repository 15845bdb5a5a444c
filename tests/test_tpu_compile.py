"""Ahead-of-time TPU compiles of the Pallas kernels at Qwen2.5-3B widths.

The interpret-mode suite (test_kernels.py) checks what the kernels
compute; it cannot see what the TPU compiler refuses: blocks that break
the (8, 128) rule, ops Mosaic cannot lower, loads from the wrong memory
space, VMEM over the scoped limit.  These tests hand each kernel to the
compiler for a described (not attached) TPU v5e chip, with shapes only.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every xdist worker
imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import decode_attention as da
from repro.kernels import sqs_fused as k

# Qwen2.5-3B: vocab 151,936, 16 query / 2 KV heads of 128; 4 serving slots
B, V, NQ, NKV, HD = 4, 151936, 16, 2, 128
S = 2048                         # dense cache length
PAGE, MAXP, POOL = 64, 32, 129   # paged: 32 pages of 64 per slot + trash


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read cache entries back: keep the
    # persistent cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _kernel_case(name):
    """(fn, [(shape, dtype), ...]) for one kernel call."""
    f32, bf16, i8, i32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32
    q = ((B, NQ, HD), bf16)
    if name == "sqs_csqs":
        return (lambda lg, beta: k.sqs_fused_call(
            lg, beta, inv_temp=1.0, ell=100, interpret=False),
            [((B, V), f32), ((B,), f32)])
    if name == "sqs_ksqs":
        return (lambda lg: k.sqs_fused_call(
            lg, None, inv_temp=1.0, ell=100, exact_k=64, interpret=False),
            [((B, V), f32)])
    if name == "topk_threshold":
        return (lambda p: k.topk_threshold_call(p, 64, interpret=False),
                [((B, V), f32)])
    if name.startswith("dense_decode"):
        kv = ((B, S, NKV, HD), i8 if name.endswith("int8") else bf16)
        args = [q, kv, kv, ((B,), i32)]
        if name.endswith("int8"):
            args += [((B, S, NKV), f32)] * 2
        return (lambda *a: da.flash_gqa_decode_call(*a, interpret=False),
                args)
    kv = ((POOL, PAGE, NKV, HD), i8 if name.endswith("int8") else bf16)
    args = [q, kv, kv, ((B, MAXP), i32), ((B,), i32)]
    if name.endswith("int8"):
        args += [((POOL, PAGE, NKV), f32)] * 2
    return (lambda *a: da.paged_flash_gqa_decode_call(*a, interpret=False),
            args)


@pytest.mark.parametrize("name", ["sqs_csqs", "sqs_ksqs", "topk_threshold",
                                  "dense_decode", "dense_decode_int8",
                                  "paged_decode", "paged_decode_int8"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name)
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
              for s, d in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 30


@pytest.mark.parametrize("fn", ["sparsify_threshold", "sparsify_topk"])
def test_sqs_core_compiles_without_sort_for_v5e(one_chip, fn):
    """The jnp SQS path (the draft step's, ``use_kernels=False``) at
    Qwen2.5-3B's vocabulary: after the TPU compiler's passes, no sort."""
    from repro.core import sqs
    f = {"sparsify_threshold": lambda q: sqs.sparsify_threshold(q, 1e-3, 100),
         "sparsify_topk": lambda q: sqs.sparsify_topk(q, 64, 100)}[fn]
    q = jax.ShapeDtypeStruct((B, V), jnp.float32, sharding=one_chip)
    text = jax.jit(f).lower(q).compile().as_text()
    assert not re.search(r"\b(sort|topk)\(", text)
    assert not re.search(r'custom_call_target="[^"]*top_?k', text, re.I)
