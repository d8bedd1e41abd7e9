"""Pure-jnp oracles for every Pallas kernel (bit-level semantics match)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def gram_ref(u: jax.Array) -> jax.Array:
    u32 = u.astype(jnp.float32)
    return u32 @ u32.T


def cross_gram_ref(u: jax.Array, v: jax.Array) -> jax.Array:
    return u.astype(jnp.float32) @ v.astype(jnp.float32).T


def weighted_aggregate_ref(w: jax.Array, updates: jax.Array, weights: jax.Array) -> jax.Array:
    return w.astype(jnp.float32) + weights.astype(jnp.float32) @ updates.astype(jnp.float32)


def topk_mask_ref(u: jax.Array, *, keep_frac: float = 0.1, block_d: int = 2048) -> jax.Array:
    """Block-local top-k with identical semantics to kernels.topk_mask."""
    (d,) = u.shape
    pad = (-d) % block_d
    up = jnp.pad(u, (0, pad)) if pad else u
    blocks = up.reshape(-1, block_d)
    k = max(1, math.ceil(keep_frac * block_d))
    mag = jnp.abs(blocks.astype(jnp.float32))
    kth = jax.lax.top_k(mag, k)[0][:, k - 1]
    keep = mag >= kth[:, None]
    out = jnp.where(keep, blocks, jnp.zeros_like(blocks)).reshape(-1)
    return out[:d]


def decode_attention_ref(
    q: jax.Array,        # (B, H, hd)
    k_cache: jax.Array,  # (B, S, K, hd)
    v_cache: jax.Array,  # (B, S, K, hd)
    length: jax.Array,   # (B,) valid cache lengths
    *,
    scale: float | None = None,
) -> jax.Array:
    """GQA single-token decode attention oracle. Returns (B, H, hd)."""
    b, h, hd = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    group = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qf = q.astype(jnp.float32) * scale
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    # expand kv heads to query heads
    qg = qf.reshape(b, kv, group, hd)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, kf)
    mask = jnp.arange(s)[None, :] < length[:, None]          # (B, S)
    logits = jnp.where(mask[:, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, vf)
    return out.reshape(b, h, hd)


def moe_gmm_ref(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
                transpose_rhs: bool = False) -> jax.Array:
    """Grouped product oracle: ``lax.ragged_dot`` over rows sorted by group,
    operands in their own dtype, accumulated in float32."""
    if transpose_rhs:
        rhs = jnp.swapaxes(rhs, 1, 2)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32)
