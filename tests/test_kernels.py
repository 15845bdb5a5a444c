"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes / temperatures / K / ℓ, plus independent sort-based
oracles for the bisection top-K."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sqs as core_sqs
from repro.kernels import ops, ref
from repro.kernels import sqs_fused as k


def _logits(key, B, V, scale=3.0):
    return jax.random.normal(key, (B, V), jnp.float32) * scale


@pytest.mark.parametrize("B,V", [(1, 128), (4, 1000), (2, 4096),
                                 (3, 50257), (1, 152064)])
@pytest.mark.parametrize("temp", [0.5, 1.0])
def test_sqs_threshold_kernel_vs_ref(B, V, temp):
    logits = _logits(jax.random.PRNGKey(B * V), B, V)
    beta = jnp.full((B,), 2e-3, jnp.float32)
    rk = ops.sqs_threshold(logits, beta, temperature=temp, ell=100)
    rr = ops.sqs_threshold(logits, beta, temperature=temp, ell=100,
                           use_ref=True)
    np.testing.assert_array_equal(np.asarray(rk.q_hat),
                                  np.asarray(rr.q_hat))
    np.testing.assert_array_equal(np.asarray(rk.mask), np.asarray(rr.mask))
    np.testing.assert_allclose(np.asarray(rk.dropped),
                               np.asarray(rr.dropped), atol=1e-6)
    # exact lattice: sum b == ell
    np.testing.assert_array_equal(
        np.round(np.asarray(rk.q_hat) * 100).sum(-1), 100)


@pytest.mark.parametrize("V,K,ell", [(1000, 8, 100), (1000, 64, 100),
                                     (4096, 16, 50), (50257, 256, 1000),
                                     (512, 1, 100)])
def test_sqs_topk_kernel_vs_ref_and_core(V, K, ell):
    B = 3
    logits = _logits(jax.random.PRNGKey(V + K), B, V)
    rk = ops.sqs_topk(logits, K, ell=ell)
    rr = ops.sqs_topk(logits, K, ell=ell, use_ref=True)
    np.testing.assert_array_equal(np.asarray(rk.q_hat), np.asarray(rr.q_hat))
    np.testing.assert_array_equal(np.asarray(rk.K), K)
    # agreement with the XLA top_k based core path
    q = core_sqs.softmax_temp(logits, 1.0)
    rc = core_sqs.sparsify_topk(q, K, ell)
    np.testing.assert_allclose(np.asarray(rk.q_hat), np.asarray(rc.q_hat),
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(rk.dropped),
                               np.asarray(rc.dropped), atol=1e-5)


@pytest.mark.parametrize("V,K", [(1000, 1), (1000, 10), (1000, 999),
                                 (4096, 64)])
def test_bisection_brackets_kth_largest(V, K):
    """Independent sort-based oracle: the K-th largest value must lie in
    the bisection bracket [lo, hi), with count(q >= lo) >= K.  (lo == kth
    exactly once values are separated by more than max(q)/2^40; in the
    far tail the deviation is bounded by K * 2^-40 probability mass —
    below one lattice unit for any practical ℓ.)"""
    q = jax.nn.softmax(_logits(jax.random.PRNGKey(K), 4, V), axis=-1)
    tau = np.asarray(k.topk_threshold_call(q, K, interpret=True))
    kth = np.asarray(ref.kth_largest_ref(q, K))
    assert np.all(tau[:, 0] <= kth + 1e-12)
    assert np.all(kth <= tau[:, 1] + 1e-12)
    # width converges to fp32 ulp at the kth value's magnitude (midpoint
    # arithmetic stalls at adjacent floats) or to max(q)/2^40, whichever
    # is larger
    res = np.maximum(np.asarray(q.max(-1)) / 2.0 ** 40,
                     4 * np.spacing(kth.astype(np.float32)))
    assert np.all(tau[:, 1] - tau[:, 0] <= np.maximum(res, 1e-12))
    cnt = np.asarray((q >= tau[:, 0:1]).sum(-1))
    assert np.all(cnt >= K)


def test_dtype_sweep_bf16_logits():
    """bf16 inputs: wrapper upcasts; kernel and ref must still agree."""
    logits = _logits(jax.random.PRNGKey(0), 2, 2048).astype(jnp.bfloat16)
    beta = jnp.full((2,), 1e-3, jnp.float32)
    rk = ops.sqs_threshold(logits.astype(jnp.float32), beta, ell=100)
    rr = ops.sqs_threshold(logits.astype(jnp.float32), beta, ell=100,
                           use_ref=True)
    np.testing.assert_array_equal(np.asarray(rk.q_hat), np.asarray(rr.q_hat))


def test_unpadded_vs_padded_vocab():
    """V not a lane multiple: padding must not change results."""
    V = 1003                          # prime-ish, forces padding
    logits = _logits(jax.random.PRNGKey(5), 2, V)
    beta = jnp.full((2,), 1e-3, jnp.float32)
    rk = ops.sqs_threshold(logits, beta, ell=100)
    q = core_sqs.softmax_temp(logits, 1.0)
    rc = core_sqs.sparsify_threshold(q, beta[:, None], 100)
    np.testing.assert_allclose(np.asarray(rk.q_hat), np.asarray(rc.q_hat),
                               atol=2e-6)
    assert rk.q_hat.shape == (2, V)


def test_select_n_exactness():
    """The in-VMEM exact-sum corrector: always returns exactly n."""
    rng = np.random.default_rng(0)
    for trial in range(20):
        Vp = 256
        v = jnp.asarray(rng.uniform(-0.5, 0.5, (1, Vp)), jnp.float32)
        elig = jnp.asarray(rng.random((1, Vp)) < 0.4)
        n_el = int(np.asarray(elig).sum())
        n = jnp.asarray([[float(rng.integers(0, n_el + 1))]], jnp.float32)
        sel = k._select_n(v, elig, n)
        assert int(np.asarray(sel).sum()) == int(n[0, 0])
        assert not np.any(np.asarray(sel) & ~np.asarray(elig))


@pytest.mark.parametrize("B,S,nkv,qpk,hd",
                         [(2, 1024, 2, 4, 64), (1, 512, 1, 8, 128),
                          (3, 2000, 4, 1, 128), (2, 384, 8, 2, 64)])
def test_flash_decode_kernel_vs_ref(B, S, nkv, qpk, hd):
    from repro.kernels.decode_attention import quantize_kv
    nq = nkv * qpk
    key = jax.random.PRNGKey(S)
    q = jax.random.normal(key, (B, nq, hd), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, nkv, hd))
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, nkv, hd))
    pos = jnp.asarray(np.arange(B) * 7 + S // 2, jnp.int32)
    out = ops.gqa_decode(q, kc, vc, pos)
    r = ops.gqa_decode(q, kc, vc, pos, use_ref=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=2e-5)
    # int8 path: kernel must equal the dequantised oracle exactly-ish,
    # and quantization noise must stay small
    k8, ks = quantize_kv(kc)
    v8, vs = quantize_kv(vc)
    out8 = ops.gqa_decode(q, k8, v8, pos, ks, vs)
    r8 = ops.gqa_decode(q, k8, v8, pos, ks, vs, use_ref=True)
    np.testing.assert_allclose(np.asarray(out8), np.asarray(r8), atol=2e-5)
    assert float(jnp.max(jnp.abs(out8 - r))) < 0.02


@pytest.mark.parametrize("nkv,qpk,hd,ps,maxp,n_pages",
                         [(2, 4, 64, 16, 8, 20), (1, 8, 128, 32, 4, 6),
                          (4, 1, 64, 8, 16, 40)])
def test_paged_flash_decode_kernel_vs_ref(nkv, qpk, hd, ps, maxp, n_pages):
    """Paged kernel: the grid walks each slot's LOGICAL page list and the
    scalar-prefetched page table picks the physical pool row.  Must match
    the gather-then-dense oracle, fp and int8, including trash-page
    entries past the allocation."""
    from repro.kernels.decode_attention import quantize_kv
    B = 3
    nq = nkv * qpk
    P = n_pages + 1                            # + trash page
    rng = np.random.default_rng(nkv * hd + ps)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, nq, hd), jnp.float32)
    pool_k = jax.random.normal(jax.random.PRNGKey(1), (P, ps, nkv, hd))
    pool_v = jax.random.normal(jax.random.PRNGKey(2), (P, ps, nkv, hd))
    # disjoint per-slot page lists in a shuffled physical order; entries
    # beyond each slot's allocation point at the trash page
    perm = rng.permutation(n_pages)
    pt = np.full((B, maxp), n_pages, np.int32)
    used, pos = 0, []
    for b in range(B):
        npg = int(rng.integers(1, min(maxp, n_pages - used - (B - 1 - b))
                               + 1))
        pt[b, :npg] = perm[used:used + npg]
        used += npg
        pos.append(npg * ps - int(rng.integers(1, ps)))
    pt = jnp.asarray(pt)
    pos = jnp.asarray(pos, jnp.int32)
    out = ops.paged_gqa_decode(q, pool_k, pool_v, pt, pos)
    r = ops.paged_gqa_decode(q, pool_k, pool_v, pt, pos, use_ref=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=2e-5)
    # agreement with the DENSE kernel on the gathered cache: paging must
    # not change the math, only the addressing
    gk = pool_k[pt].reshape(B, maxp * ps, nkv, hd)
    gv = pool_v[pt].reshape(B, maxp * ps, nkv, hd)
    dense = ops.gqa_decode(q, gk, gv, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=2e-5)
    # int8 pools with per-(position, head) scale side tables
    k8, ks = quantize_kv(pool_k)
    v8, vs = quantize_kv(pool_v)
    out8 = ops.paged_gqa_decode(q, k8, v8, pt, pos, ks, vs)
    r8 = ops.paged_gqa_decode(q, k8, v8, pt, pos, ks, vs, use_ref=True)
    np.testing.assert_allclose(np.asarray(out8), np.asarray(r8), atol=2e-5)
    assert float(jnp.max(jnp.abs(out8 - r))) < 0.02


def test_flash_decode_bf16_cache():
    nq, nkv, hd, B, S = 8, 2, 64, 2, 640
    q = jax.random.normal(jax.random.PRNGKey(0), (B, nq, hd), jnp.float32)
    kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, nkv, hd),
                           jnp.bfloat16)
    vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, nkv, hd),
                           jnp.bfloat16)
    pos = jnp.asarray([S - 1, S // 3], jnp.int32)
    out = ops.gqa_decode(q, kc, vc, pos)
    r = ops.gqa_decode(q, kc, vc, pos, use_ref=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(r), atol=5e-3)
