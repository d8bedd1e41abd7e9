"""GQA single-token decode attention Pallas kernel (flash-decoding on TPU).

The serving hot spot for the decode shapes (decode_32k, long_500k): one query
token per sequence attends over a KV cache of up to 524 288 positions.  The
computation is memory-bound (arithmetic intensity ~= 2 flops/byte), so the
kernel's job is to stream the cache through VMEM exactly once.

TPU adaptation: grid = (batch, S/BLOCK_S).  Each step loads a
(BLOCK_S, K, hd) cache tile (trailing dim 128-aligned), computes grouped-query
logits with one MXU matmul, and maintains an online-softmax running
(max, denom, acc) in VMEM scratch — the classic flash decomposition, blocked
for VMEM rather than for SM shared memory.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

DEFAULT_BLOCK_S = 512
_NEG_INF = -1e30


def _decode_attn_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, block_s
):
    sblk = pl.program_id(1)
    nblk = pl.num_programs(1)

    @pl.when(sblk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale        # (H, hd)
    k = k_ref[0].astype(jnp.float32)                # (BS, K, hd)
    v = v_ref[0].astype(jnp.float32)                # (BS, K, hd)
    h, hd = q.shape
    kv = k.shape[1]
    group = h // kv

    qg = q.reshape(kv, group, hd)                   # (K, G, hd)
    # logits[k, g, s] = <q[k,g,:], cache_k[s,k,:]>
    logits = jax.lax.dot_general(
        qg,
        k,
        (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32,
    )                                               # (K, G, BS)

    length = len_ref[0, 0]
    pos = sblk * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_s), 2)
    logits = jnp.where(pos < length, logits, _NEG_INF)

    m_prev = m_ref[...]                             # (K, G)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    probs = jnp.exp(logits - m_new[..., None])      # (K, G, BS)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(probs, axis=-1)
    # acc[k, g, :] += probs[k, g, :] @ v[:, k, :]
    pv = jax.lax.dot_general(
        probs,
        v,
        (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32,
    )                                               # (K, G, hd)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
    m_ref[...] = m_new

    @pl.when(sblk == nblk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        out = (acc_ref[...] / denom).reshape(h, hd)
        o_ref[...] = out[None].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(
    q: jax.Array,        # (B, H, hd)
    k_cache: jax.Array,  # (B, S, K, hd)
    v_cache: jax.Array,  # (B, S, K, hd)
    length: jax.Array,   # (B,) int32 valid lengths
    *,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One-token GQA attention over a blocked KV cache.  Returns (B, H, hd).

    ``interpret=None`` resolves from the detected JAX backend."""
    if interpret is None:
        interpret = default_interpret()
    b, h, hd = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    if h % kv:
        raise ValueError(f"H={h} not divisible by K={kv}")
    if s % block_s:
        pad = (-s) % block_s
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s += pad
    scale = 1.0 / math.sqrt(hd)
    group = h // kv
    grid = (b, s // block_s)
    return pl.pallas_call(
        functools.partial(_decode_attn_kernel, scale=scale, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, h, hd), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_s, kv, hd), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, block_s, kv, hd), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, hd), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((kv, group), jnp.float32),
            pltpu.VMEM((kv, group), jnp.float32),
            pltpu.VMEM((kv, group, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
    )(length.reshape(b, 1).astype(jnp.int32), q, k_cache, v_cache)
