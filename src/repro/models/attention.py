"""Attention blocks: full/local (sliding-window) GQA with chunked (flash-style)
training attention and cached decode.

Training/prefill attention is computed with an online-softmax scan over KV
chunks, so peak memory is O(S * chunk) instead of O(S²) — mandatory for the
prefill_32k shape, and the same decomposition the Pallas decode kernel uses
(kernels/decode_attention.py validates the blocked algorithm bit-for-bit at
small shapes).  MLA's causal training attention runs a Pallas flash kernel
instead (kernels/mla_attention.py), which visits only the blocks on or below
the diagonal.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.mla_attention import BLOCK as MLA_BLOCK, mla_attention
from repro.models.layers import (
    apply_norm,
    apply_rope,
    apply_rope_yarn,
    dense,
    dense_init,
    init_norm,
    yarn_mscale,
)

_NEG_INF = -1e30
DEFAULT_KV_CHUNK = 1024


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_attention(rng, cfg: ArchConfig, dtype, cross: bool = False) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    if cross:
        kv = h  # whisper cross-attention is MHA
    rq, rk, rv, ro = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(rq, d, h * hd, dtype),
        "wk": dense_init(rk, d, kv * hd, dtype),
        "wv": dense_init(rv, d, kv * hd, dtype),
        "wo": dense_init(ro, h * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def _project_qkv(params, x, x_kv, cfg: ArchConfig, cross: bool):
    h, hd = cfg.num_heads, cfg.resolved_head_dim
    kv = h if cross else cfg.num_kv_heads
    q = x @ params["wq"]
    k = x_kv @ params["wk"]
    v = x_kv @ params["wv"]
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    b, s = x.shape[:2]
    skv = x_kv.shape[1]
    return (
        q.reshape(b, s, h, hd),
        k.reshape(b, skv, kv, hd),
        v.reshape(b, skv, kv, hd),
    )


# ---------------------------------------------------------------------------
# chunked (flash-style) attention over full sequences
# ---------------------------------------------------------------------------
def _chunk_attend(q, k, v, mask, scale):
    """q: (B,S,K,G,hd)  k/v: (B,C,K,hd)  mask: (B,S,C) bool -> (out, m, l)."""
    logits = jnp.einsum("bskgd,bckd->bskgc", q.astype(jnp.float32), k.astype(jnp.float32))
    logits = logits * scale
    logits = jnp.where(mask[:, :, None, None, :], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1)                               # (B,S,K,G)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bskgc,bckd->bskgd", p, v.astype(jnp.float32))
    return out, m, l


def chunked_attention(
    q: jax.Array,           # (B, S, H, hd)
    k: jax.Array,           # (B, Skv, K, hd)
    v: jax.Array,
    q_positions: jax.Array,  # (B, S) absolute positions of queries
    kv_positions: jax.Array,  # (B, Skv)
    *,
    causal: bool,
    window: int = 0,
    kv_chunk: int = DEFAULT_KV_CHUNK,
    scale: Optional[float] = None,
) -> jax.Array:
    """Online-softmax attention, scanning KV in chunks.  Returns (B,S,H,hd_v)
    (``hd_v`` is v's head size, which may differ from q's and k's);
    ``scale`` defaults to ``1/sqrt(hd)``.  The backward pass recomputes each
    chunk's scores instead of keeping every chunk's (B, S, H, C)
    probabilities (0.5 GB a chunk at 8 x 2,048 positions, 16 heads and
    C = 512)."""
    b, s, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    group = h // kvh
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    qg = q.reshape(b, s, kvh, group, hd)

    # pad KV to a chunk multiple; padded positions get -1 (always masked)
    pad = (-skv) % kv_chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)), constant_values=-1)
    n_chunks = (skv + pad) // kv_chunk
    k_chunks = k.reshape(b, n_chunks, kv_chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    v_chunks = v.reshape(b, n_chunks, kv_chunk, kvh, hd_v).transpose(1, 0, 2, 3, 4)
    p_chunks = kv_positions.reshape(b, n_chunks, kv_chunk).transpose(1, 0, 2)

    def body(carry, xs):
        acc, m_run, l_run = carry
        kc, vc, pc = xs
        valid = pc >= 0                                         # (B, C)
        mask = valid[:, None, :]                                # (B, 1, C)
        mask = jnp.broadcast_to(mask, (b, s, kv_chunk))
        if causal:
            mask = mask & (pc[:, None, :] <= q_positions[:, :, None])
        if window > 0:
            mask = mask & (pc[:, None, :] > q_positions[:, :, None] - window)
        out_c, m_c, l_c = _chunk_attend(qg, kc, vc, mask, scale)
        m_new = jnp.maximum(m_run, m_c)
        alpha = jnp.exp(m_run - m_new)
        beta = jnp.exp(m_c - m_new)
        acc = acc * alpha[..., None] + out_c * beta[..., None]
        l_run = l_run * alpha + l_c * beta
        return (acc, m_new, l_run), None

    acc0 = jnp.zeros((b, s, kvh, group, hd_v), jnp.float32)
    m0 = jnp.full((b, s, kvh, group), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s, kvh, group), jnp.float32)
    (acc, _, l), _ = jax.lax.scan(jax.checkpoint(body), (acc0, m0, l0),
                                  (k_chunks, v_chunks, p_chunks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, s, h, hd_v).astype(q.dtype)


# ---------------------------------------------------------------------------
# block-level apply (train / prefill)
# ---------------------------------------------------------------------------
def attention_block(
    params,
    x: jax.Array,
    positions: jax.Array,
    cfg: ArchConfig,
    *,
    local: bool,
    encoder_out: Optional[jax.Array] = None,
    use_rope: bool = True,
) -> jax.Array:
    """Self (or cross) attention sub-block, without norms/residual."""
    cross = encoder_out is not None
    x_kv = encoder_out if cross else x
    q, k, v = _project_qkv(params, x, x_kv, cfg, cross)
    if use_rope and not cross:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    b, s = x.shape[:2]
    if cross:
        kv_pos = jnp.broadcast_to(jnp.arange(x_kv.shape[1])[None], (b, x_kv.shape[1]))
        out = chunked_attention(q, k, v, positions, kv_pos, causal=False, window=0)
    else:
        out = chunked_attention(
            q, k, v, positions, positions, causal=True,
            window=cfg.window if local else 0,
        )
    return out.reshape(b, s, -1) @ params["wo"]


# ---------------------------------------------------------------------------
# decode with KV cache
# ---------------------------------------------------------------------------
def init_kv_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype) -> Dict:
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cache_len, kv, hd), dtype),
        "v": jnp.zeros((batch, cache_len, kv, hd), dtype),
    }


def decode_attention_jnp(q, k_cache, v_cache, length, *, window: int = 0, ring: bool = False):
    """One-token GQA attention over the cache (same math as the Pallas kernel).

    q: (B, H, hd); caches: (B, S, K, hd); length: (B,) tokens written so far
    (current token already written).  With ``ring=True`` the cache is a ring
    buffer (sliding-window decode) and every *written* slot is valid.
    """
    b, h, hd = q.shape
    s, kvh = k_cache.shape[1], k_cache.shape[2]
    group = h // kvh
    scale = 1.0 / math.sqrt(hd)
    qg = (q.astype(jnp.float32) * scale).reshape(b, kvh, group, hd)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache.astype(jnp.float32))
    slot = jnp.arange(s)[None, :]
    if ring:
        valid = slot < jnp.minimum(length, s)[:, None]
    else:
        valid = slot < length[:, None]
        if window > 0:
            valid = valid & (slot >= length[:, None] - window)
    logits = jnp.where(valid[:, None, None, :], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, h, hd).astype(q.dtype)


def attention_decode_step(
    params,
    x_t: jax.Array,            # (B, 1, D)
    cache: Dict,
    position: jax.Array,       # scalar int32: index of this token
    cfg: ArchConfig,
    *,
    local: bool,
    use_rope: bool = True,
    cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, Dict]:
    """One decode step.  For local blocks the cache is a ring buffer of
    ``min(window, cache_len)``; for global blocks it is full-length."""
    b = x_t.shape[0]
    if cross_kv is not None:
        h, hd = cfg.num_heads, cfg.resolved_head_dim
        q = (x_t @ params["wq"])
        if "bq" in params:
            q = q + params["bq"]
        q = q.reshape(b, h, hd)
        k_enc, v_enc = cross_kv
        enc_len = jnp.full((b,), k_enc.shape[1], jnp.int32)
        out = decode_attention_jnp(q, k_enc, v_enc, enc_len)
        return out.reshape(b, 1, -1) @ params["wo"], cache

    q, k, v = _project_qkv(params, x_t, x_t, cfg, cross=False)
    pos = jnp.reshape(position, (1, 1)).astype(jnp.int32)
    if use_rope:
        q = apply_rope(q, jnp.broadcast_to(pos, (b, 1)), cfg.rope_theta)
        k = apply_rope(k, jnp.broadcast_to(pos, (b, 1)), cfg.rope_theta)
    cache_len = cache["k"].shape[1]
    window = cfg.window if local else 0
    # ring buffer when the cache is sized by the window; otherwise the cache
    # is full-length and windowing (if any) is applied by masking.
    ring = bool(window) and cache_len <= window
    slot = position % cache_len
    k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
    length = jnp.full((b,), position + 1, jnp.int32)
    out = decode_attention_jnp(
        q[:, 0], k_cache, v_cache, length, window=window, ring=ring
    )
    new_cache = {"k": k_cache, "v": v_cache}
    return out.reshape(b, 1, -1) @ params["wo"], new_cache


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------
def init_mla(rng, cfg: ArchConfig, dtype) -> Dict:
    """MLA's projections, named as DeepSeek-V2's modeling code names them:
    ``q_proj`` (D, H·(nope+rope)), ``kv_a_proj`` (D, kv_lora + rope; the
    ``kv_a_proj_with_mqa``), ``kv_norm`` (the ``kv_a_layernorm``),
    ``kv_b_proj`` (kv_lora, H·(nope+v)) and ``o_proj`` (H·v, D)."""
    m, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    rq, ra, rb, ro = jax.random.split(rng, 4)
    return {
        "q_proj": dense_init(rq, d, h * m.qk_head_dim, dtype),
        "kv_a_proj": dense_init(ra, d, m.kv_lora_rank + m.qk_rope_head_dim, dtype),
        "kv_norm": init_norm("rmsnorm", m.kv_lora_rank, dtype),
        "kv_b_proj": dense_init(rb, m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim), dtype),
        "o_proj": dense_init(ro, h * m.v_head_dim, d, dtype),
    }


def mla_softmax_scale(cfg: ArchConfig) -> float:
    """``qk_head_dim^-0.5``, times YaRN's ``mscale(factor, mscale_all_dim)²``
    where the rope is scaled (0.11472 for DeepSeek-V2-Lite)."""
    scale = cfg.mla.qk_head_dim ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale *= yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


def _mla_rope(x: jax.Array, positions: jax.Array, cfg: ArchConfig) -> jax.Array:
    if cfg.rope_scaling is None:
        # same de-interleaved layout as the YaRN path, at factor 1
        from repro.configs.base import YarnScaling

        return apply_rope_yarn(x, positions, cfg.rope_theta, YarnScaling(1.0, 1))
    return apply_rope_yarn(x, positions, cfg.rope_theta, cfg.rope_scaling)


def _mla_qkv(params, x: jax.Array, positions: jax.Array, cfg: ArchConfig):
    """Queries (B,S,H,nope+rope), the normalized latent (B,S,kv_lora) and the
    roped shared key part (B,S,rope)."""
    m, h = cfg.mla, cfg.num_heads
    b, s = x.shape[:2]
    q = dense(x, params["q_proj"]).reshape(b, s, h, m.qk_head_dim)
    q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    ckv = dense(x, params["kv_a_proj"])
    c, k_pe = ckv[..., : m.kv_lora_rank], ckv[..., m.kv_lora_rank:]
    c = apply_norm("rmsnorm", params["kv_norm"], c)
    q_pe = _mla_rope(q_pe, positions, cfg)
    k_pe = _mla_rope(k_pe[:, :, None, :], positions, cfg)[:, :, 0, :]
    return jnp.concatenate([q_nope, q_pe], axis=-1), c, k_pe


def _mla_kv(params, c: jax.Array, k_pe: jax.Array, cfg: ArchConfig):
    """Keys (B,L,H,nope+rope) and values (B,L,H,v) from the latent."""
    m, h = cfg.mla, cfg.num_heads
    b, l = c.shape[:2]
    kv = dense(c, params["kv_b_proj"]).reshape(b, l, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = kv[..., : m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (b, l, h, m.qk_rope_head_dim))
    return jnp.concatenate([k_nope, k_pe.astype(k_nope.dtype)], axis=-1), v


def mla_block(params, x: jax.Array, positions: jax.Array, cfg: ArchConfig,
              kv_chunk: int = MLA_BLOCK) -> jax.Array:
    """Causal MLA over the whole sequence (training / prefill), without the
    norms and residual: ``q = x·W_q``; ``[c_kv, k_pe] = x·W_kva``;
    ``[k_nope, v] = RMSNorm(c_kv)·W_kvb``; YaRN RoPE on ``q_pe`` and the
    shared ``k_pe``; causal flash attention at ``mla_softmax_scale``
    (``kernels/mla_attention.py``, query and KV blocks of ``kv_chunk`` rows);
    ``o·W_o``.

    The kernel masks by each row's index, not by ``positions``: the two
    agree because every caller (``TransformerLM.hidden``, for training and
    eval) passes each row's ``arange``.  ``positions`` feed YaRN RoPE."""
    b, s = x.shape[:2]
    with jax.named_scope("mla/proj"):
        q, c, k_pe = _mla_qkv(params, x, positions, cfg)
        k, v = _mla_kv(params, c, k_pe, cfg)
    with jax.named_scope("mla/attend"):
        out = mla_attention(q, k, v, scale=mla_softmax_scale(cfg), block=kv_chunk)
    with jax.named_scope("mla/proj"):
        return dense(out.reshape(b, s, -1), params["o_proj"])


def init_mla_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype) -> Dict:
    """The latent cache: the normalized ``c_kv`` and the roped ``k_pe``."""
    m = cfg.mla
    return {"ckv": jnp.zeros((batch, cache_len, m.kv_lora_rank), dtype),
            "kpe": jnp.zeros((batch, cache_len, m.qk_rope_head_dim), dtype)}


def mla_decode_step(params, x_t: jax.Array, cache: Dict, position: jax.Array,
                    cfg: ArchConfig) -> Tuple[jax.Array, Dict]:
    """One decode step over the latent cache; keys and values are expanded
    from the latents of every cached position."""
    b = x_t.shape[0]
    pos = jnp.broadcast_to(jnp.reshape(position, (1, 1)).astype(jnp.int32), (b, 1))
    q, c, k_pe = _mla_qkv(params, x_t, pos, cfg)
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], c.astype(cache["ckv"].dtype), position, axis=1)
    kpe = jax.lax.dynamic_update_slice_in_dim(cache["kpe"], k_pe.astype(cache["kpe"].dtype), position, axis=1)
    k, v = _mla_kv(params, ckv, kpe, cfg)
    logits = jnp.einsum("bhd,blhd->bhl", q[:, 0].astype(jnp.float32),
                        k.astype(jnp.float32)) * mla_softmax_scale(cfg)
    valid = jnp.arange(ckv.shape[1])[None, None, :] <= position
    p = jax.nn.softmax(jnp.where(valid, logits, _NEG_INF), axis=-1)
    out = jnp.einsum("bhl,blhd->bhd", p, v.astype(jnp.float32)).astype(x_t.dtype)
    return dense(out.reshape(b, 1, -1), params["o_proj"]), {"ckv": ckv, "kpe": kpe}
