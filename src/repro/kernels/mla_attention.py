"""Causal flash attention for multi-head latent attention (``mla_attention``).

``out[b, i, h] = Σ_{j ≤ i} softmax_j(scale · q[b, i, h]·k[b, j, h]) v[b, j, h]``
for q, k (B, S, H, qk) and v (B, S, H, v_dim): MLA's unequal head sizes
(qk 192 = 128 + 64 under RoPE, v 128 in DeepSeek-V2) taken as they are.
Position ``i`` is a row's index: the mask is causal by index.

Three kernels, tied together by a ``custom_vjp``:

* ``mla_attention_fwd``: the output and each row's max ``M`` and sum ``l``
  (an online softmax over the row's KV blocks: running max and sum in
  float32);
* ``mla_attention_dq``: dQ, recomputing each visited block's scores;
* ``mla_attention_dkv``: dK and dV, from the same recomputed blocks, in the
  transposed orientation (keys on sublanes) so the ``M`` and
  ``rowsum(dO·O) / l`` rows broadcast without a relayout.

Every block's products are those of the parent ``chunked_attention``'s KV
chunk: the probabilities reach the MXU relative to the block's own row max,
``p = exp(s - m_blk)`` (so each block's largest is exactly 1), and the
block's factor ``beta = exp(m_blk - M)`` multiplies what they are multiplied
with: the P·V product in the forward, and ``dO / l`` before its rounding in
the backward.  A probability normalised first would reach the MXU rounded.

Each kernel's grid is (batch × head, visit).  The visits are scalar-prefetched
lists of (query block, KV block) pairs on or below the diagonal (see
:func:`visits`): blocks above it are never loaded or computed, and only the
diagonal blocks apply the element mask.  Scores and
probabilities live only in VMEM, one (block, block) tile at a time.

Products run on the MXU with float32 accumulation.  Compiled (TPU), float32
operands go to the MXU as bf16, as XLA's DEFAULT precision gives the plain
``jnp.einsum`` on a TPU (one bf16 pass); interpreted (every other backend)
they stay float32, as XLA's CPU gives them.  The softmax scale multiplies the
float32 scores, and the float32 dS before its rounding.  Under ``vmap`` the
mapped axis is folded into the batch (:func:`repro.kernels.fold_vmap`), so a
mapped call is one kernel call.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret, fold_vmap

# query and KV rows per block; S is padded to a whole number of blocks
BLOCK = 512
# scoped VMEM the kernels may use: at 512 x 512 blocks the dK/dV kernel's
# double-buffered q, k, v, dO blocks, its accumulators and four (512, 512)
# float32 tiles take ~10 MiB (v5e has 128 MiB)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_MASKED = -1e30
_NT = (((1,), (1,)), ((), ()))          # a · bᵀ
_NN = (((1,), (0,)), ((), ()))          # a · b


def visits(num_blocks: int, *, kv_major: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """(query block, KV block) of each visit: the pairs on or below the
    diagonal, query-major (each query block's KV blocks in order), or
    KV-major with ``kv_major``."""
    pairs = [(i, j) for i in range(num_blocks) for j in range(i + 1)]
    if kv_major:
        pairs.sort(key=lambda p: (p[1], p[0]))
    qi, kj = zip(*pairs)
    return np.asarray(qi, np.int32), np.asarray(kj, np.int32)


def _causal(s, *, kv_major=False):
    """A diagonal block's scores ``s`` (queries, keys), or their transpose
    with ``kv_major``, with the keys after each query set to ``_MASKED``."""
    rows = lax.broadcasted_iota(jnp.int32, s.shape, 1 if kv_major else 0)
    cols = lax.broadcasted_iota(jnp.int32, s.shape, 0 if kv_major else 1)
    return jnp.where(cols <= rows, s, _MASKED)


def _on_diagonal(step, i, j):
    """``step(masked)`` for visit (i, j): masked on the diagonal only."""
    pl.when(i == j)(lambda: step(True))
    pl.when(i != j)(lambda: step(False))


def _row(x, block):
    """A (block, 1) column as the (1, block) row of a per-row output."""
    return jnp.transpose(jnp.broadcast_to(x, (block, 128)))[:1]


def _col(x):
    """A (1, block) row as a (block, 1) column."""
    return jnp.transpose(jnp.broadcast_to(x, (128, x.shape[1])))[:, :1]


def _fwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, m_sc, l_sc, acc_sc,
                *, scale, block):
    t = pl.program_id(1)
    i, j = qi_ref[t], kj_ref[t]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _MASKED)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked):
        s = lax.dot_general(q_ref[...], k_ref[...], _NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal(s)
        m_prev = m_sc[...]
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        alpha = jnp.exp(m_prev - m_new)
        beta = jnp.exp(m_blk - m_new)
        p = jnp.exp(s - m_blk)
        l_sc[...] = alpha * l_sc[...] + beta * jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + beta * lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], _NN, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    _on_diagonal(step, i, j)

    @pl.when(j == i)
    def _finish():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[...] = (acc_sc[...] / l).astype(o_ref.dtype)
        m_ref[...] = _row(m_sc[...], block)
        l_ref[...] = _row(l, block)


def _dq_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, m_ref, di_ref, dq_ref, dq_sc,
               *, scale):
    t = pl.program_id(1)
    i, j = qi_ref[t], kj_ref[t]

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def step(masked):
        s = lax.dot_general(q_ref[...], k_ref[...], _NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal(s)
        m_blk = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m_blk)
        beta = jnp.exp(m_blk - jnp.expand_dims(m_ref[0], -1))
        do = (do_ref[...] * beta).astype(v_ref.dtype)
        dp = lax.dot_general(do, v_ref[...], _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.expand_dims(di_ref[0], -1) * beta) * scale
        dq_sc[...] += lax.dot_general(ds.astype(k_ref.dtype), k_ref[...], _NN,
                                      preferred_element_type=jnp.float32)

    _on_diagonal(step, i, j)

    @pl.when(j == i)
    def _finish():
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, m_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale, num_blocks):
    t = pl.program_id(1)
    i, j = qi_ref[t], kj_ref[t]

    @pl.when(i == j)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(masked):
        # transposed: keys on sublanes, queries on lanes
        st = lax.dot_general(k_ref[...], q_ref[...], _NT,
                             preferred_element_type=jnp.float32) * scale
        if masked:
            st = _causal(st, kv_major=True)
        m_blk = jnp.max(st, axis=0, keepdims=True)
        pt = jnp.exp(st - m_blk)
        beta = jnp.exp(m_blk - m_ref[...])
        do = (do_ref[...] * _col(beta)).astype(v_ref.dtype)
        dv_sc[...] += lax.dot_general(pt.astype(v_ref.dtype), do, _NN,
                                      preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[...], do, _NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[...] * beta) * scale
        dk_sc[...] += lax.dot_general(dst.astype(q_ref.dtype), q_ref[...], _NN,
                                      preferred_element_type=jnp.float32)

    _on_diagonal(step, i, j)

    @pl.when(i == num_blocks - 1)
    def _finish():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _q_spec(block, width):
    """A (block, width) block of a (N, S, width) array at the visit's query block."""
    return pl.BlockSpec((None, block, width), lambda n, t, qi, kj: (n, qi[t], 0))


def _kv_spec(block, width):
    """A (block, width) block of a (N, S, width) array at the visit's KV block."""
    return pl.BlockSpec((None, block, width), lambda n, t, qi, kj: (n, kj[t], 0))


def _row_spec(block):
    """A (1, block) block of a (N, 1, S) per-row array at the visit's query block."""
    return pl.BlockSpec((None, 1, block), lambda n, t, qi, kj: (n, 0, qi[t]))


def _forward_call(q, k, v, *, scale, block, interpret):
    """o (N, S, v_dim) float32 and the rows' max and sum (N, 1, S) of
    head-major q, k, v."""
    n, s, dqk = q.shape
    dv = v.shape[-1]
    qi, kj = visits(s // block)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block=block),
        out_shape=(jax.ShapeDtypeStruct((n, s, dv), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1, s), jnp.float32),
                   jax.ShapeDtypeStruct((n, 1, s), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, len(qi)),
            in_specs=[_q_spec(block, dqk), _kv_spec(block, dqk), _kv_spec(block, dv)],
            out_specs=[_q_spec(block, dv), _row_spec(block), _row_spec(block)],
            scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, dv), jnp.float32)]),
        compiler_params=_params(),
        interpret=interpret,
        name="mla_attention_fwd",
    )(jnp.asarray(qi), jnp.asarray(kj), q, k, v)


def _backward_call(q, k, v, do, m, di, *, scale, block, interpret):
    """dq, dk, dv (float32, head-major) from head-major q, k, v, float32
    ``dO / l``, and the rows' max and ``rowsum(dO·O) / l`` (N, 1, S)."""
    n, s, dqk = q.shape
    dv = v.shape[-1]
    num_blocks = s // block
    in_specs = [_q_spec(block, dqk), _kv_spec(block, dqk), _kv_spec(block, dv),
                _q_spec(block, dv), _row_spec(block), _row_spec(block)]

    qi, kj = visits(num_blocks)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        out_shape=jax.ShapeDtypeStruct((n, s, dqk), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n, len(qi)), in_specs=in_specs,
            out_specs=_q_spec(block, dqk),
            scratch_shapes=[pltpu.VMEM((block, dqk), jnp.float32)]),
        compiler_params=_params(),
        interpret=interpret,
        name="mla_attention_dq",
    )(jnp.asarray(qi), jnp.asarray(kj), q, k, v, do, m, di)

    qi, kj = visits(num_blocks, kv_major=True)
    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, num_blocks=num_blocks),
        out_shape=(jax.ShapeDtypeStruct((n, s, dqk), jnp.float32),
                   jax.ShapeDtypeStruct((n, s, dv), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n, len(qi)), in_specs=in_specs,
            out_specs=[_kv_spec(block, dqk), _kv_spec(block, dv)],
            scratch_shapes=[pltpu.VMEM((block, dqk), jnp.float32),
                            pltpu.VMEM((block, dv), jnp.float32)]),
        compiler_params=_params(),
        interpret=interpret,
        name="mla_attention_dkv",
    )(jnp.asarray(qi), jnp.asarray(kj), q, k, v, do, m, di)
    return dq, dk, dv_


def _head_major(x, dtype, s_pad):
    """(B, S, H, d) -> (B·H, S_pad, d) in ``dtype``, zero rows after S."""
    b, s, h, d = x.shape
    x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, s, d).astype(dtype)
    return jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0))) if s_pad > s else x


def _seq_major(x, b, s):
    """(B·H, S_pad, d) -> (B, S, H, d)."""
    bh, _, d = x.shape
    return jnp.transpose(x[:, :s].reshape(b, bh // b, s, d), (0, 2, 1, 3))


def _rows(x, s_pad):
    """(B, S, H) -> (B·H, 1, S_pad), zeros after S."""
    b, s, h = x.shape
    x = jnp.transpose(x, (0, 2, 1)).reshape(b * h, 1, s)
    return jnp.pad(x, ((0, 0), (0, 0), (0, s_pad - s))) if s_pad > s else x


def _geometry(s, block, interpret):
    """The block (no longer than S), S padded to a whole number of blocks,
    and the MXU operands' dtype."""
    block = min(block, s)
    return block, -(-s // block) * block, jnp.float32 if interpret else jnp.bfloat16


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention(q, k, v, scale, block, interpret):
    return _attention_fwd(q, k, v, scale, block, interpret)[0]


def _attention_fwd(q, k, v, scale, block, interpret):
    s = q.shape[1]
    block, s_pad, dt = _geometry(s, block, interpret)

    def impl(q, k, v):
        b = q.shape[0]
        o, m, l = _forward_call(*(_head_major(x, dt, s_pad) for x in (q, k, v)),
                                scale=scale, block=block, interpret=interpret)
        return (_seq_major(o, b, s).astype(q.dtype),
                m.reshape(b, -1, 1, s_pad), l.reshape(b, -1, 1, s_pad))

    out, m, l = fold_vmap(impl, 3)(q, k, v)
    return out, (q, k, v, out, m, l)


def _attention_bwd(scale, block, interpret, res, d_out):
    q, k, v, out, m, l = res
    s = q.shape[1]
    block, s_pad, dt = _geometry(s, block, interpret)

    def impl(q, k, v, out, m, l, d_out):
        b = q.shape[0]
        l = l.reshape(-1, 1, s_pad)
        do = _head_major(d_out, jnp.float32, s_pad) / jnp.swapaxes(l, 1, 2)
        di = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
        dq, dk, dv = _backward_call(
            *(_head_major(x, dt, s_pad) for x in (q, k, v)), do,
            m.reshape(-1, 1, s_pad), _rows(di, s_pad) / l,
            scale=scale, block=block, interpret=interpret)
        return tuple(_seq_major(g, b, s).astype(x.dtype)
                     for g, x in ((dq, q), (dk, k), (dv, v)))

    return fold_vmap(impl, 7)(q, k, v, out, m, l, d_out)


_attention.defvjp(_attention_fwd, _attention_bwd)


def mla_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float,
                  block: int = BLOCK, interpret: Optional[bool] = None) -> jax.Array:
    """Causal attention of q, k (B, S, H, qk) and v (B, S, H, v_dim), the
    mask by each row's index: (B, S, H, v_dim) in q's dtype.  A block longer
    than S shrinks to S; S is padded to a whole number of blocks (the padded
    keys follow every real query, so the causal mask hides them)."""
    if interpret is None:
        interpret = default_interpret()
    return _attention(q, k, v, float(scale), int(block), bool(interpret))
