"""MLA's causal flash-attention kernel (``kernels/mla_attention.py``),
interpreted on the CPU at lane-aligned shapes: S = 512, 2 heads, qk 192
(128 + 64), v 128, blocks of 128.

* the output and the gradients with respect to q, k and v match
  ``chunked_attention`` and a float32 ``Precision.HIGHEST`` einsum;
* the same under ``vmap`` over a client axis, as one kernel call;
* the kernel visits exactly the block pairs on or below the diagonal;
* with bf16 MXU operands, as compiled on a TPU, its products are those of
  ``chunked_attention``'s KV chunks under TPU's DEFAULT precision.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.kernels import mla_attention as ma
from repro.models.attention import chunked_attention

S, H, DQK, DV, BLOCK = 512, 2, 192, 128, 128
SCALE = 0.11472


def _inputs(seed, *lead, s=S):
    r = np.random.default_rng(seed)
    shape = (*lead, s, H)
    q, k = (jnp.asarray(r.normal(size=(*shape, DQK)), jnp.float32) for _ in range(2))
    v, ct = (jnp.asarray(r.normal(size=(*shape, DV)), jnp.float32) for _ in range(2))
    return q, k, v, ct


def _kernel(q, k, v, block=BLOCK):
    return ma.mla_attention(q, k, v, scale=SCALE, block=block)


def _chunked(q, k, v):
    b, s = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return chunked_attention(q, k, v, pos, pos, causal=True, kv_chunk=BLOCK, scale=SCALE)


def _highest(q, k, v):
    s = q.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=lax.Precision.HIGHEST) * SCALE
    logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, axis=-1), v,
                      precision=lax.Precision.HIGHEST)


def _pallas_calls(jaxpr):
    """The ``pallas_call`` equations of a jaxpr and of the jaxprs inside it."""
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            yield e
        for p in e.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _pallas_calls(sub)


def _value_and_grads(f, q, k, v, ct):
    out, back = jax.vjp(f, q, k, v)
    return (out, *back(ct))


@pytest.mark.parametrize("reference", [_chunked, _highest], ids=["chunked", "highest"])
@pytest.mark.parametrize("s,block", [(S, BLOCK), (200, 64)], ids=["aligned", "padded"])
def test_output_and_grads_match(reference, s, block):
    """S = 200 pads to 256 with blocks of 64: the padded keys follow every
    real query and the padded rows' cotangent is zero."""
    q, k, v, ct = _inputs(0, 2, s=s)
    got = jax.jit(lambda *a: _value_and_grads(lambda q, k, v: _kernel(q, k, v, block), *a))(
        q, k, v, ct)
    want = jax.jit(lambda *a: _value_and_grads(reference, *a))(q, k, v, ct)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5, atol=2e-5,
                                   err_msg=name)


def test_vmap_over_clients_is_one_call_and_matches_each_client():
    q, k, v, ct = _inputs(1, 3, 2)                      # 3 clients x 2 rows
    both = lambda *a: _value_and_grads(jax.vmap(_kernel), *a)
    jaxpr = jax.make_jaxpr(both)(q, k, v, ct)
    calls = list(_pallas_calls(jaxpr.jaxpr))
    names = sorted(e.params["name"] for e in calls)
    assert names == ["mla_attention_dkv", "mla_attention_dq", "mla_attention_fwd"]
    # the client axis folds into the kernel's batch x head axis
    assert all(e.params["grid_mapping"].grid[0] == 3 * 2 * H for e in calls)
    got = jax.jit(both)(q, k, v, ct)
    for c in range(3):
        want = _value_and_grads(_highest, q[c], k[c], v[c], ct[c])
        for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(np.asarray(g[c]), np.asarray(w), rtol=2e-5,
                                       atol=2e-5, err_msg=f"client {c} {name}")


@pytest.mark.parametrize("s,block,visited", [(512, 128, 10), (2048, 512, 10), (2048, 256, 36),
                                             (2048, 128, 136), (256, 256, 1)])
def test_visits_only_the_lower_triangle(s, block, visited):
    n = s // block
    want = {(i, j) for i in range(n) for j in range(n) if j <= i}
    for kv_major in (False, True):
        qi, kj = ma.visits(n, kv_major=kv_major)
        pairs = list(zip(qi.tolist(), kj.tolist()))
        assert len(pairs) == len(set(pairs)) == visited
        assert set(pairs) == want
        order = sorted(pairs, key=(lambda p: (p[1], p[0])) if kv_major else None)
        assert pairs == order                               # each accumulator's run is contiguous
    # the kernels' grids run over exactly these visits
    q, k, v, ct = _inputs(2, 1, s=s)
    jaxpr = jax.make_jaxpr(lambda *a: _value_and_grads(
        lambda q, k, v: _kernel(q, k, v, block), *a))(q, k, v, ct)
    grids = [e.params["grid_mapping"].grid for e in _pallas_calls(jaxpr.jaxpr)]
    assert grids == [(H, visited)] * 3


def _bf16_dot(spec):
    """``einsum(spec)`` as TPU's DEFAULT precision runs a float32 product,
    forward and in its transposes: operands rounded to bf16, exact products
    summed in float32."""
    r = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    exact = lambda a, b: jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)

    @jax.custom_vjp
    def dot(a, b):
        return exact(r(a), r(b))

    def bwd(res, g):
        return jax.vjp(exact, *map(r, res))[1](r(g))

    dot.defvjp(lambda a, b: (dot(a, b), (a, b)), bwd)
    return dot


def _chunks_bf16(q, k, v):
    """``chunked_attention``'s causal online softmax over KV chunks of
    ``BLOCK``, its two products run as on a TPU (``_bf16_dot``)."""
    b, s, h, _ = q.shape
    n = s // BLOCK
    qk, pv = _bf16_dot("bqhd,bkhd->bqhk"), _bf16_dot("bqhk,bkhd->bqhd")
    pos = jnp.arange(s)
    chunks = lambda x: jnp.moveaxis(x.reshape(b, n, BLOCK, *x.shape[2:]), 1, 0)

    def body(carry, xs):
        acc, m_run, l_run = carry
        kc, vc, pc = xs
        logits = qk(q, kc) * SCALE
        logits = jnp.where((pc[None, :] <= pos[:, None])[None, :, None, :], logits, -1e30)
        m_c = jnp.max(logits, axis=-1)
        p = jnp.exp(logits - m_c[..., None])
        m_new = jnp.maximum(m_run, m_c)
        alpha, beta = jnp.exp(m_run - m_new), jnp.exp(m_c - m_new)
        acc = acc * alpha[..., None] + pv(p, vc) * beta[..., None]
        return (acc, m_new, l_run * alpha + jnp.sum(p, -1) * beta), None

    init = (jnp.zeros((b, s, h, v.shape[-1])), jnp.full((b, s, h), -1e30), jnp.zeros((b, s, h)))
    (acc, _, l), _ = lax.scan(jax.checkpoint(body), init,
                              (chunks(k), chunks(v), pos.reshape(n, BLOCK)))
    return acc / jnp.maximum(l, 1e-30)[..., None]


def test_bf16_products_are_the_parent_chunks(monkeypatch):
    """Interpreted with bf16 MXU operands, as compiled on a TPU: each block's
    probabilities reach the MXU relative to the block's own row max (its
    largest exactly 1), and the block's factor scales dO before rounding, as
    in the parent's chunks.  The output and dV then equal the parent's
    arithmetic to ~1e-5; probabilities normalised first, or taken relative to
    the row's final max, read 5e-4 to 3e-3.  dQ and dK differ at bf16's
    rounding of dS (the parent's carries the max's gradient)."""
    geometry = ma._geometry
    monkeypatch.setattr(ma, "_geometry", lambda s, block, interpret:
                        (*geometry(s, block, interpret)[:2], jnp.bfloat16))
    q, k, v, ct = _inputs(5, 2)
    got = jax.jit(lambda *a: _value_and_grads(_kernel, *a))(q, k, v, ct)
    want = jax.jit(lambda *a: _value_and_grads(_chunks_bf16, *a))(q, k, v, ct)
    for name, g, w, limit in zip(("out", "dq", "dk", "dv"), got, want,
                                 (5e-5, 1e-2, 1e-2, 3e-4)):
        gap = float(np.linalg.norm(np.asarray(g) - np.asarray(w)) / np.linalg.norm(np.asarray(w)))
        assert gap < limit, (name, gap)
