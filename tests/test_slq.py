"""SLQ (Algorithm 2) unit + property tests."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.slq import lattice_quantize, tv_distance
from repro.core.sqs import dense_qs, sparsify_threshold, sparsify_topk


def random_sparse_dist(rng, V, K):
    q = np.zeros(V, np.float32)
    idx = rng.choice(V, K, replace=False)
    vals = rng.random(K).astype(np.float32) + 1e-3
    q[idx] = vals / vals.sum()
    return q, idx


@pytest.mark.parametrize("V,K,ell", [(64, 8, 100), (1024, 32, 100),
                                     (1024, 32, 7), (4096, 256, 1000),
                                     (64, 1, 100), (64, 64, 50)])
def test_sum_exact(V, K, ell):
    rng = np.random.default_rng(0)
    for trial in range(5):
        q, _ = random_sparse_dist(rng, V, K)
        q_hat, b = lattice_quantize(jnp.asarray(q), ell)
        assert int(np.asarray(b).sum()) == ell
        assert np.all(np.asarray(b) >= 0)
        np.testing.assert_allclose(np.asarray(q_hat).sum(), 1.0, rtol=1e-5)


@pytest.mark.parametrize("V,K,ell", [(256, 16, 100), (256, 16, 25),
                                     (1024, 128, 100)])
def test_tv_bound(V, K, ell):
    """Paper eq. (20): TV(q̃, q̂) ≤ K/(4ℓ)."""
    rng = np.random.default_rng(1)
    for trial in range(10):
        q, _ = random_sparse_dist(rng, V, K)
        q_hat, _ = lattice_quantize(jnp.asarray(q), ell)
        tv = float(tv_distance(jnp.asarray(q), q_hat))
        assert tv <= K / (4.0 * ell) + 1e-5, (tv, K / (4 * ell))


def test_lattice_point_fixed():
    """Distributions already on the lattice are unchanged."""
    ell = 100
    q = jnp.asarray([0.25, 0.5, 0.13, 0.12, 0.0, 0.0], jnp.float32)
    q_hat, b = lattice_quantize(q, ell)
    np.testing.assert_allclose(np.asarray(q_hat), np.asarray(q), atol=1e-6)


def test_batched():
    rng = np.random.default_rng(2)
    qs = np.stack([random_sparse_dist(rng, 128, 16)[0] for _ in range(7)])
    q_hat, b = lattice_quantize(jnp.asarray(qs), 100)
    assert q_hat.shape == qs.shape
    np.testing.assert_array_equal(np.asarray(b).sum(-1), 100)


@settings(max_examples=40, deadline=None)
@given(st.integers(2, 200), st.integers(1, 50), st.integers(5, 500),
       st.integers(0, 2**31 - 1))
def test_property_sum_and_support(V, K, ell, seed):
    K = min(K, V)
    rng = np.random.default_rng(seed)
    q, idx = random_sparse_dist(rng, V, K)
    q_hat, b = lattice_quantize(jnp.asarray(q), ell)
    b = np.asarray(b)
    assert b.sum() == ell
    assert b.min() >= 0
    off = np.setdiff1d(np.arange(V), idx)
    assert b[off].sum() == 0, "mass outside the support"


# ----------------------------------------------------------------------
# The sort-free selection against Algorithm 2's rank form
# ----------------------------------------------------------------------
def _ranks(x):
    """rank[i] = position of x[i] in a stable ascending sort."""
    return jnp.argsort(jnp.argsort(x, axis=-1), axis=-1)


@functools.partial(jax.jit, static_argnums=1)
def _lattice_counts_by_ranks(q_tilde, ell, mask):
    """The exact-sum correction as two full rankings of ζ (the oracle)."""
    q = q_tilde.astype(jnp.float32)
    b = jnp.where(mask, jnp.floor(ell * q + 0.5), 0.0)
    zeta = b - ell * q
    delta = (b.sum(-1) - ell)[..., None]
    zeta_dec = jnp.where(mask & (b > 0), zeta, -jnp.inf)
    zeta_inc = jnp.where(mask, zeta, jnp.inf)
    dec = (_ranks(-zeta_dec) < delta) & mask & (b > 0)
    inc = (_ranks(zeta_inc) < -delta) & mask
    return (b - dec + inc).astype(jnp.int32), delta[..., 0]


def _support_by_sort(q, K):
    """K-SQS support on one row: the K-th value of a sort, ``q >= kth``,
    ties cut by index (the benchmark's numpy rule)."""
    K = min(K, q.shape[0])
    kth = np.sort(q)[::-1][K - 1]
    mask = q >= kth
    return mask & (np.cumsum(mask) <= K)


def _rows(kind, V, ell, rng):
    """(q, mask) for 4 rows of one kind; q a full distribution."""
    from repro.core.sqs import softmax_temp
    logits = rng.normal(size=(4, V)).astype(np.float32) * 3
    beta = {"csqs": 1e-3, "dense": -1e-3, "ties": 1e-4}.get(kind)
    if kind == "ties":                          # rounded logits, T 1
        q = softmax_temp(jnp.asarray(np.round(logits)), 1.0)
    elif kind == "dense":                       # flat, whole vocabulary
        q = softmax_temp(jnp.asarray(logits / 3), 1.0)
    elif kind == "delta0":                      # already on the lattice
        counts = np.zeros((4, V), np.float32)
        for r in range(4):
            np.add.at(counts[r], rng.integers(0, V, ell), 1.0)
        q = jnp.asarray(counts / ell)
        return q, q > 0
    else:                                       # C-SQS at T 0.2
        q = softmax_temp(jnp.asarray(logits), 0.2)
    top1 = jax.nn.one_hot(q.argmax(-1), V, dtype=jnp.bool_)
    return q, (q >= beta) | top1


@pytest.mark.parametrize("kind", ["csqs", "dense", "delta0", "ties"])
@pytest.mark.parametrize("ell", [1, 100])
@pytest.mark.parametrize("V", [7, 1000, 151936])
def test_select_matches_rank_oracle(V, ell, kind):
    """Lattice counts equal the two-ranking form of Algorithm 2 bit for
    bit, and K-SQS's support equals the sort rule, ties and K >= V
    included."""
    rng = np.random.default_rng([V, ell, len(kind)])
    q, mask = _rows(kind, V, ell, rng)
    q_tilde = jnp.where(mask, q, 0.0)
    q_tilde = q_tilde / q_tilde.sum(-1, keepdims=True)
    want, delta = _lattice_counts_by_ranks(q_tilde, ell, mask)
    _, got = jax.jit(lattice_quantize, static_argnums=1)(q_tilde, ell, mask)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got).sum(-1), ell)
    delta = np.asarray(delta)
    if kind == "delta0":
        assert (delta == 0).all()
    if kind == "dense" and V == 151936:
        assert (delta == -ell).all()            # whole vocabulary, δ = −ℓ
    K = 64
    r = jax.jit(sparsify_topk, static_argnums=(1, 2))(q, K, ell)
    qn = np.asarray(q)
    for i in range(qn.shape[0]):
        np.testing.assert_array_equal(np.asarray(r.mask[i]),
                                      _support_by_sort(qn[i], K))


@pytest.mark.parametrize("fn", ["sparsify_threshold", "sparsify_topk",
                                "dense_qs"])
def test_sqs_lowers_without_sort(fn):
    """No vocabulary-wide sort or top-k in the SQS programs at Qwen2.5-3B's
    vocabulary: the lattice correction and the K-th value are selections."""
    f = {"sparsify_threshold": lambda q: sparsify_threshold(q, 1e-3, 100),
         "sparsify_topk": lambda q: sparsify_topk(q, 64, 100),
         "dense_qs": lambda q: dense_qs(q, 100)}[fn]
    text = jax.jit(f).lower(
        jax.ShapeDtypeStruct((4, 151936), jnp.float32)).as_text()
    assert not re.search(r"\b(stablehlo\.sort|chlo\.top_k)\b", text)
