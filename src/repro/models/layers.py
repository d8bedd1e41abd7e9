"""Shared neural-net layers for the assigned-architecture zoo (pure JAX).

Conventions: params are nested dicts of arrays; every ``init_*`` takes an rng
and returns params; every ``apply`` is a pure function.  Activations run in
the config dtype; norms and softmax accumulate in fp32.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
def dense_init(rng, fan_in: int, fan_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (scale * jax.random.normal(rng, (fan_in, fan_out), jnp.float32)).astype(dtype)


def embed_init(rng, vocab: int, d: int, dtype):
    return (0.02 * jax.random.normal(rng, (vocab, d), jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def init_norm(kind: str, d: int, dtype):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    raise ValueError(f"unknown norm {kind}")


def apply_norm(kind: str, params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    elif kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        out = out * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    else:
        raise ValueError(f"unknown norm {kind}")
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------
def activation(name: str, x: jax.Array) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if name == "relu2":  # nemotron squared-ReLU
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(f"unknown activation {name}")


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------
def dense(x: jax.Array, w) -> jax.Array:
    """``x @ w`` for a projection weight ``w``: a plain array, or a factored
    one (``repro.models.lora.Factored``: ``x@W + s·(x@A)@B``, never merged).
    Where ``w`` is held in a narrower dtype than ``x`` (bf16 weights under a
    float32 residual stream), ``x`` is cast to it and the product accumulates
    in float32."""
    if not isinstance(w, jax.Array) and hasattr(w, "project"):
        return w.project(x)
    if x.dtype != w.dtype and jnp.dtype(w.dtype).itemsize < jnp.dtype(x.dtype).itemsize:
        return jnp.dot(x.astype(w.dtype), w, preferred_element_type=x.dtype)
    return x @ w


# ---------------------------------------------------------------------------
# MLP (gated / plain)
# ---------------------------------------------------------------------------
def init_mlp(rng, d: int, f: int, gated: bool, dtype):
    r1, r2, r3 = jax.random.split(rng, 3)
    params = {"wi": dense_init(r1, d, f, dtype), "wo": dense_init(r2, f, d, dtype)}
    if gated:
        params["wg"] = dense_init(r3, d, f, dtype)
    return params


def apply_mlp(params, x: jax.Array, act: str) -> jax.Array:
    h = dense(x, params["wi"])
    if "wg" in params:
        h = activation(act, dense(x, params["wg"])) * h
    else:
        h = activation(act, h)
    return dense(h, params["wo"])


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature factor, ``0.1·mscale·ln(factor) + 1``."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, scaling) -> jax.Array:
    """YaRN inverse frequencies (``rope_scaling`` type ``yarn``), as
    DeepSeek-V2's modeling code computes them: the extrapolated
    frequencies above the correction range, the interpolated ones
    (``/ factor``) below it, and a linear ramp between."""
    def correction_dim(rotations):
        return (dim * math.log(scaling.original_max_position / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    inter = extra / scaling.factor
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                      # 1: extrapolate, 0: interpolate
    return inter * (1.0 - keep) + extra * keep


def apply_rope_yarn(x: jax.Array, positions: jax.Array, theta: float, scaling) -> jax.Array:
    """RoPE over ``x``'s last dim with YaRN frequencies.  As DeepSeek-V2's
    ``apply_rotary_pos_emb``, the dims are first de-interleaved (even dims,
    then odd), then rotated by halves; cos and sin carry YaRN's
    ``mscale / mscale_all_dim`` factor.  x: (..., S, H, d); positions (..., S)."""
    d = x.shape[-1]
    inv = yarn_frequencies(d, theta, scaling)
    m = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
        scaling.factor, scaling.mscale_all_dim)
    angles = positions[..., None].astype(jnp.float32) * inv        # (..., S, d/2)
    cos = (jnp.cos(angles) * m)[..., None, :]
    sin = (jnp.sin(angles) * m)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]                           # de-interleave
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# temporal conv (RG-LRU block frontend; width-4 causal depthwise conv)
# ---------------------------------------------------------------------------
def init_conv1d(rng, d: int, width: int, dtype):
    return {
        "w": (jax.random.normal(rng, (width, d), jnp.float32) / math.sqrt(width)).astype(dtype),
        "b": jnp.zeros((d,), dtype),
    }


def apply_conv1d(params, x: jax.Array) -> jax.Array:
    """Causal depthwise conv over (B, S, D)."""
    width = params["w"].shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(width):
        out = out + pad[:, i : i + x.shape[1], :].astype(jnp.float32) * params["w"][i].astype(jnp.float32)
    return (out + params["b"].astype(jnp.float32)).astype(x.dtype)


def conv1d_decode(params, x_t: jax.Array, tail: jax.Array):
    """One-step causal conv.  x_t: (B, 1, D); tail: (B, width-1, D) history."""
    width = params["w"].shape[0]
    window = jnp.concatenate([tail, x_t], axis=1)             # (B, width, D)
    out = jnp.einsum("bwd,wd->bd", window.astype(jnp.float32), params["w"].astype(jnp.float32))
    out = (out + params["b"].astype(jnp.float32)).astype(x_t.dtype)[:, None, :]
    new_tail = window[:, 1:, :]
    return out, new_tail
