"""Pairwise Gram-matrix Pallas kernel — the FLrce relationship-modeling hot spot.

``G = U @ U.T`` for ``U ∈ R^{P×D}`` where P is the number of participating
clients per round (small, padded to the MXU sublane multiple) and D is the
flattened model dimension (huge — up to 1.3e11 for dbrx-132b).  One pass over
U yields every pairwise dot product and every squared norm (diag), from which
all of Eq. 5 (cosine similarity) and Algorithm 3 (conflict counting) follow.

TPU adaptation (DESIGN.md §6): instead of a GPU-style per-pair dot-product
kernel, each grid step loads one (P, BLOCK_D) tile into VMEM and issues a
single MXU matmul, accumulating the (P, P) Gram tile in fp32.  BLOCK_D is
128-lane aligned; the grid walks D so arbitrarily large models stream through
VMEM.
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
# rows of V per cross_gram tile: the lane (last) dim of the (K, BLOCK_M)
# output tile, so a multiple of 128; a (512, 2048) fp32 V tile is 4 MiB
BLOCK_M = 512


def _gram_kernel(u_ref, out_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...].astype(jnp.float32)
    out_ref[...] += jax.lax.dot_general(
        u, u, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gram(
    u: jax.Array, *, block_d: int = DEFAULT_BLOCK_D, interpret: Optional[bool] = None
) -> jax.Array:
    """Gram matrix ``u @ u.T`` in fp32 via a D-blocked Pallas kernel.

    ``u``: (P, D).  D is zero-padded to a multiple of ``block_d`` (zero columns
    do not change the Gram matrix).  ``interpret=None`` resolves from the
    detected JAX backend (compiled on TPU, interpreted elsewhere).
    """
    if interpret is None:
        interpret = default_interpret()
    p, d = u.shape
    pad = (-d) % block_d
    if pad:
        u = jnp.pad(u, ((0, 0), (0, pad)))
    d_padded = d + pad
    grid = (d_padded // block_d,)
    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((p, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((p, p), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, p), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(u)


def _xgram_kernel(u_ref, v_ref, out_ref, *, d: int, block_d: int):
    step = pl.program_id(1)                      # D is the reduction axis

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    if d % block_d:
        # the last D tile overhangs the arrays: zero the columns past d
        col = step * block_d + jax.lax.broadcasted_iota(jnp.int32, (1, block_d), 1)
        u = jnp.where(col < d, u, 0.0)
        v = jnp.where(col < d, v, 0.0)
    out_ref[...] += jax.lax.dot_general(
        u, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def cross_gram(
    u: jax.Array, v: jax.Array, *, block_d: int = DEFAULT_BLOCK_D,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Cross Gram ``u @ v.T`` for (K, D) x (M, D) — the relationship block's
    dots of fresh updates against the stored update/anchor maps.

    The grid tiles both the rows of V (``BLOCK_M``, "parallel") and D
    ("arbitrary", the reduction), so VMEM holds one ``(BLOCK_M, block_d)``
    tile of V whatever M is.  Neither operand is padded: V is the (M, D)
    map, and a padded copy of it would double its HBM footprint.  The
    overhanging tail of the last D tile is masked to zero in the kernel,
    and a ragged last row tile only produces output columns that are never
    written back.  ``interpret=None`` resolves from the detected JAX
    backend.
    """
    if interpret is None:
        interpret = default_interpret()
    if u.shape[1] != v.shape[1]:
        raise ValueError(f"dim mismatch {u.shape} vs {v.shape}")
    k, d = u.shape
    m = v.shape[0]
    bm = min(m, BLOCK_M)
    return pl.pallas_call(
        functools.partial(_xgram_kernel, d=d, block_d=block_d),
        grid=(pl.cdiv(m, bm), pl.cdiv(d, block_d)),
        in_specs=[
            pl.BlockSpec((k, block_d), lambda i, j: (0, j)),
            pl.BlockSpec((bm, block_d), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((k, bm), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, m), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(u, v)
