"""Per-kernel shape/dtype sweeps: Pallas (interpreted on CPU) vs pure-jnp oracle.

Hypothesis property tests live in test_properties.py (dev-only dependency).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

DTYPES = [jnp.float32, jnp.bfloat16]


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)


@pytest.mark.parametrize("p", [2, 8, 10])
@pytest.mark.parametrize("d", [128, 1000, 4096])
@pytest.mark.parametrize("dtype", DTYPES)
def test_gram_matches_ref(p, d, dtype):
    rng = np.random.default_rng(p * d)
    u = _rand(rng, (p, d), dtype)
    got = ops.gram(u)
    want = ref.gram_ref(u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2 if dtype == jnp.bfloat16 else 2e-4, atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("p,q,d", [
    (4, 6, 512), (8, 8, 3000),
    (10, 700, 5000),   # two row tiles of V (the last ragged) and a ragged D tile
])
def test_cross_gram_matches_ref(p, q, d):
    rng = np.random.default_rng(p + q)
    u = _rand(rng, (p, d), jnp.float32)
    v = _rand(rng, (q, d), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.cross_gram(u, v)), np.asarray(ref.cross_gram_ref(u, v)),
        rtol=2e-4, atol=1e-4,
    )


@pytest.mark.parametrize("p,d", [(3, 100), (10, 5000), (16, 16384)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_aggregate_matches_ref(p, d, dtype):
    rng = np.random.default_rng(d)
    w = _rand(rng, (d,), jnp.float32)
    u = _rand(rng, (p, d), dtype)
    weights = jnp.asarray(rng.dirichlet(np.ones(p)), jnp.float32)
    got = ops.weighted_aggregate(w, u, weights)
    want = ref.weighted_aggregate_ref(w, u, weights)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 2e-4,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_aggregate_is_eq4():
    """Eq. 4 sanity: aggregation of identical updates returns w + u."""
    d = 300
    w = jnp.zeros((d,))
    u = jnp.ones((4, d))
    weights = jnp.full((4,), 0.25)
    out = ops.weighted_aggregate(w, u, weights)
    np.testing.assert_allclose(np.asarray(out), np.ones(d), rtol=1e-6)


@pytest.mark.parametrize("d,keep,block", [(4096, 0.1, 512), (5000, 0.25, 1024), (100, 1.0, 128)])
def test_topk_mask_matches_ref(d, keep, block):
    rng = np.random.default_rng(d)
    u = _rand(rng, (d,), jnp.float32)
    got = ops.topk_mask(u, keep_frac=keep, block_d=block)
    want = ref.topk_mask_ref(u, keep_frac=keep, block_d=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("p,d,keep,block,kind", [
    (5, 700, 0.1, 256, "normal"),       # P and D both padded
    (8, 4096, 0.25, 1024, "normal"),
    (3, 2048, 0.1, 2048, "ties"),       # many equal magnitudes, ±0.0
    (10, 3000, 0.05, 512, "bf16"),
])
def test_topk_mask_rows_bitwise_matches_ref(p, d, keep, block, kind):
    """The bisection threshold is the exact k-th magnitude: every row is
    bitwise the lax.top_k oracle, ties at the threshold included."""
    rng = np.random.default_rng(p * d)
    if kind == "ties":
        u = jnp.asarray(rng.integers(-3, 4, size=(p, d)), jnp.float32)
        u = u.at[0, :10].set(-0.0)
    else:
        dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
        u = _rand(rng, (p, d), dtype)
    got = np.asarray(ops.topk_mask_rows(u, keep_frac=keep, block_d=block))
    for i in range(p):
        want = np.asarray(ref.topk_mask_ref(u[i], keep_frac=keep, block_d=block))
        np.testing.assert_array_equal(got[i], want)


@pytest.mark.parametrize("b,h,kv,hd,s,block", [
    (2, 8, 2, 64, 512, 128),
    (1, 4, 4, 128, 300, 128),   # MHA + padded S
    (3, 16, 1, 64, 1024, 256),  # MQA
])
def test_decode_attention_matches_ref(b, h, kv, hd, s, block):
    rng = np.random.default_rng(b * s)
    q = _rand(rng, (b, h, hd), jnp.float32)
    k = _rand(rng, (b, s, kv, hd), jnp.float32)
    v = _rand(rng, (b, s, kv, hd), jnp.float32)
    length = jnp.asarray(rng.integers(1, s + 1, size=b), jnp.int32)
    got = ops.decode_attention(q, k, v, length, block_s=block)
    want = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-4, atol=3e-5)


def test_decode_attention_bf16():
    rng = np.random.default_rng(7)
    b, h, kv, hd, s = 2, 8, 4, 64, 256
    q = _rand(rng, (b, h, hd), jnp.bfloat16)
    k = _rand(rng, (b, s, kv, hd), jnp.bfloat16)
    v = _rand(rng, (b, s, kv, hd), jnp.bfloat16)
    length = jnp.asarray([100, 256], jnp.int32)
    got = ops.decode_attention(q, k, v, length)
    want = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=3e-2, atol=3e-2
    )
