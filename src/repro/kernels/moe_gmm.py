"""Grouped matrix product for dropless MoE experts (``moe_gmm``).

``out[rows of group e] = lhs[rows of group e] @ rhs[e]`` for ``lhs`` (m, k)
whose rows are sorted by group (expert) and ``group_sizes`` (E,) that sum to
m: each routed (token, expert) pair is multiplied by its own expert's
weights once, with no capacity and no padding per expert.

Built on the pattern of ``jax.experimental.pallas.ops.tpu.megablox``: the m
axis is cut into tiles of ``tm`` rows, and the grid visits, for each n tile,
every (m tile, group) pair that shares a row — a tile that straddles two
groups is visited once for each, and each visit stores only its group's
rows.  The visit list (which group, which m tile) is scalar-prefetched, so
each step loads its expert's (k, tn) weight block directly.  Consecutive
tiles of one group keep the same weight block, which is then not fetched
again.  The whole k is one block (k is a model width here, 1,408 or 2,048),
so there is no reduction axis and no accumulator.

``transpose_rhs`` multiplies by ``rhs[e].T`` (rhs (E, n, k)): the backward
pass's dX.  There is no weight gradient kernel: the experts are frozen
(``repro.models.moe``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

# rows per m tile: the m axis must be padded to a multiple of this
TILE_M = 512
# the widest n block; wider outputs are cut into blocks of this
MAX_TILE_N = 2048
# scoped VMEM the kernel may use: the double-buffered lhs, weight and output
# blocks at (512, 2048) x (2048, 1408) take ~22 MiB (v5e has 128 MiB)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def tile_m(m: int) -> int:
    """The m tile for ``m`` rows: ``TILE_M``, or ``m`` rounded up to 8 when
    smaller."""
    return min(TILE_M, -(-m // 8) * 8)


def padded_rows(m: int) -> int:
    """``m`` rounded up to a whole number of m tiles."""
    tm = tile_m(m)
    return -(-m // tm) * tm


def group_metadata(group_sizes: jax.Array, m: int, tm: int):
    """(group offsets (E+1,), each visit's group (T,), each visit's m tile
    (T,)), and the number of visits; T = m/tm + E - 1 bounds the visits
    (every group may add one partial tile), the rest repeat the last one.
    Empty groups are not visited."""
    e = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends]).astype(jnp.int32)
    starts = offsets[:-1]
    tiles = ((ends + tm - 1) // tm) - starts // tm
    tiles = jnp.where(group_sizes == 0, 0, tiles).astype(jnp.int32)
    tiles_m = m // tm
    total = tiles_m + e - 1
    group_ids = jnp.repeat(jnp.arange(e, dtype=jnp.int32), tiles, total_repeat_length=total)
    # a tile is visited once by the group owning its first row, and once
    # more by each non-empty group that starts inside it
    partial = jnp.where((starts % tm == 0) | (group_sizes == 0), tiles_m, starts // tm)
    visits = jnp.zeros(tiles_m + 1, jnp.int32).at[partial].add(1)[:tiles_m] + 1
    m_tile_ids = jnp.repeat(jnp.arange(tiles_m, dtype=jnp.int32), visits,
                            total_repeat_length=total)
    return (offsets, group_ids, m_tile_ids), jnp.sum(tiles).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "interpret"))
def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
        transpose_rhs: bool = False, interpret: Optional[bool] = None) -> jax.Array:
    """Grouped product of ``lhs`` (m, k), rows sorted by group, with
    ``rhs`` (E, k, n) (or (E, n, k) with ``transpose_rhs``); ``group_sizes``
    (E,) int32 must sum to m, and m must be a multiple of ``tile_m(m)``
    (see :func:`padded_rows`).  Operands in their own dtype (bf16 on the
    MXU), products accumulated and returned in float32."""
    if interpret is None:
        interpret = default_interpret()
    m, k = lhs.shape
    e = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = tile_m(m)
    if m % tm:
        raise ValueError(f"m={m} is not a multiple of the m tile {tm}")
    tn = min(n, MAX_TILE_N)
    tiles_n = -(-n // tn)
    meta, num_visits = group_metadata(group_sizes.astype(jnp.int32), m, tm)
    grid = (tiles_n, m // tm + e - 1)
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (((1,), (0,)), ((), ()))

    def kernel(offsets, group_ids, m_tile_ids, visits, lhs_ref, rhs_ref, out_ref):
        v = pl.program_id(1)

        @pl.when(v < visits[0])
        def _visit():
            acc = lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                                  preferred_element_type=jnp.float32)
            g = group_ids[v]
            rows = lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + m_tile_ids[v] * tm
            mine = (rows >= offsets[g]) & (rows < offsets[g + 1])
            out_ref[...] = jnp.where(mine, acc, out_ref[...])

    def lhs_map(j, v, offsets, group_ids, m_tile_ids, visits):
        return m_tile_ids[v], 0

    def rhs_map(j, v, offsets, group_ids, m_tile_ids, visits):
        return (group_ids[v], j, 0) if transpose_rhs else (group_ids[v], 0, j)

    def out_map(j, v, offsets, group_ids, m_tile_ids, visits):
        return m_tile_ids[v], j

    rhs_block = (None, tn, k) if transpose_rhs else (None, k, tn)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[pl.BlockSpec((tm, k), lhs_map), pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_gmm",
    )(*meta, num_visits[None], lhs, rhs)

