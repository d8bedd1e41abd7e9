"""Fused weighted-aggregation Pallas kernel (paper Eq. 4).

``w_new = w + sum_k p_k * u_k`` over P client updates of dimension D.  A naive
implementation reads each update separately (P+1 HBM passes); the kernel
streams one (P, BLOCK_D) tile of stacked updates plus the matching (BLOCK_D,)
slice of the global model per grid step — a single fused pass.

The weighted reduction over the (small) P axis is a (1, P) x (P, BLOCK_D)
MXU matmul with fp32 accumulation, so the kernel is purely memory-bound, as
the roofline for Eq. 4 dictates.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

DEFAULT_BLOCK_D = 4096


def _aggregate_kernel(w_ref, u_ref, p_ref, out_ref):
    w = w_ref[...].astype(jnp.float32)          # (1, BD)
    u = u_ref[...].astype(jnp.float32)          # (P, BD)
    p = p_ref[...].astype(jnp.float32)          # (1, P)
    out_ref[...] = w + jax.lax.dot_general(
        p, u, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def weighted_aggregate(
    w: jax.Array,
    updates: jax.Array,
    weights: jax.Array,
    *,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Eq. 4: ``w + weights @ updates`` with one fused pass over HBM.

    w: (D,), updates: (P, D), weights: (P,).  Returns fp32 (D,).
    ``interpret=None`` resolves from the detected JAX backend.
    """
    if interpret is None:
        interpret = default_interpret()
    (d,) = w.shape
    p, du = updates.shape
    if du != d:
        raise ValueError(f"dim mismatch: w {d} vs updates {du}")
    pad = (-d) % block_d
    if pad:
        w = jnp.pad(w, (0, pad))
        updates = jnp.pad(updates, ((0, 0), (0, pad)))
    dp = d + pad
    out = pl.pallas_call(
        _aggregate_kernel,
        grid=(dp // block_d,),
        in_specs=[
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
            pl.BlockSpec((p, block_d), lambda i: (0, i)),
            pl.BlockSpec((1, p), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(w.reshape(1, dp), updates, weights.reshape(1, p))
    return out[0, :d]
