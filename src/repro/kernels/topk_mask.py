"""Block-local magnitude top-k sparsification kernel (Fedcom baseline hot spot).

Fedcom-style compressors keep the k largest-magnitude entries of an update.
Exact global top-k needs a full sort of D elements; practical systems
(including the sparsification baselines the paper cites, e.g. [13], [17]) use
*block-local* selection: within each BLOCK_D tile keep the local top
``ceil(keep_frac * BLOCK_D)`` entries.  That is exactly expressible as a
streaming Pallas kernel: per grid step, load an (8, BLOCK_D) tile, find each
row's k-th magnitude, and zero everything below it.

Mosaic has no sort or ``top_k``, so the k-th magnitude is found by bisection
over the int32 bit pattern of ``|u|``: for non-negative floats that pattern
orders exactly as the value does, and building the threshold one bit at a
time (keep bit b iff at least k entries are >= the candidate) takes 31
compare-and-count passes and lands on the k-th largest pattern exactly.  The
jnp oracle in ``ref.py`` implements the same block-local semantics with
``lax.top_k``, and kernel and oracle agree bitwise on finite inputs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

DEFAULT_BLOCK_D = 2048
_ROW_BLOCK = 8          # f32 sublane tile: rows per grid step


def _topk_mask_kernel(u_ref, out_ref, *, k: int):
    u = u_ref[...]                                           # (8, BD)
    bits = jax.lax.bitcast_convert_type(
        jnp.abs(u.astype(jnp.float32)), jnp.int32
    )
    # largest pattern with >= k entries at or above it = the k-th largest
    thr = jnp.zeros((u.shape[0], 1), jnp.int32)
    for bit in range(30, -1, -1):
        cand = thr | (1 << bit)
        count = jnp.sum((bits >= cand).astype(jnp.float32), axis=1, keepdims=True)
        thr = jnp.where(count >= k, cand, thr)
    out_ref[...] = jnp.where(bits >= thr, u, jnp.zeros_like(u))


@functools.partial(jax.jit, static_argnames=("keep_frac", "block_d", "interpret"))
def topk_mask_rows(
    u: jax.Array,
    *,
    keep_frac: float = 0.1,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Row-wise block-local top-k of a ``(P, D)`` matrix.

    Each row is sparsified independently with the same block boundaries the
    1-D :func:`topk_mask` uses, so a row of the output is bitwise the 1-D
    kernel applied to that row.  This is the cohort form Fedcom's
    device-resident update transform applies: the whole ``(P, D)`` update
    matrix is masked in one kernel launch.  P is zero-padded to a multiple
    of 8 and D to a multiple of ``block_d``; the padding is sliced off.
    ``interpret=None`` resolves from the detected JAX backend.
    """
    if interpret is None:
        interpret = default_interpret()
    if not 0.0 < keep_frac <= 1.0:
        raise ValueError(f"keep_frac must be in (0, 1], got {keep_frac}")
    p, d = u.shape
    pad_p = (-p) % _ROW_BLOCK
    pad_d = (-d) % block_d
    if pad_p or pad_d:
        u = jnp.pad(u, ((0, pad_p), (0, pad_d)))
    k = max(1, int(-(-keep_frac * block_d // 1)))  # ceil
    spec = pl.BlockSpec((_ROW_BLOCK, block_d), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_topk_mask_kernel, k=k),
        grid=((p + pad_p) // _ROW_BLOCK, (d + pad_d) // block_d),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
    )(u)
    return out[:p, :d]


@functools.partial(jax.jit, static_argnames=("keep_frac", "block_d", "interpret"))
def topk_mask(
    u: jax.Array,
    *,
    keep_frac: float = 0.1,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Keep the block-local top ``ceil(keep_frac*block_d)`` magnitudes of (D,)."""
    return topk_mask_rows(
        u[None, :], keep_frac=keep_frac, block_d=block_d, interpret=interpret
    )[0]
