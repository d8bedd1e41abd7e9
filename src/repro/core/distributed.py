"""Mesh-sharded FLrce server math for large models.

For cross-silo federated pretraining the flattened update matrix
``U ∈ R^{P×D}`` (D up to ~1.3e11 for dbrx-132b) cannot live on one device.
We shard D across every mesh axis and compute the paper's quantities from a
handful of Gram-style reductions:

* ``G = U Uᵀ``                    → every pairwise cossim (Eq. 5) + Alg. 3 conflicts
* ``s = U w``, ``a = U aᵀ`` dots  → every orthdist (Eq. 6) via
  ``orthdist(x, anchor, v)² = ||x-a||² − ⟨x-a, v⟩²/||v||²``

The local per-shard contraction is the Pallas ``gram`` kernel; the cross-shard
reduction is a single ``psum`` inside ``shard_map``.  ``flrce_round_step`` is
the jit-lowerable "paper-technique step" used by the dry-run and §Perf.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels import ops as kops

_EPS = 1e-12


def _shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with the vma check off: pallas_call outputs carry no
    varying-manual-axes metadata."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


# ---------------------------------------------------------------------------
# Flatten / unflatten at the FL boundary
# ---------------------------------------------------------------------------
def flatten_pytree(tree) -> Tuple[jax.Array, Callable]:
    """Flatten a pytree of arrays into one fp32 vector + inverse fn."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [l.shape for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    dtypes = [l.dtype for l in leaves]
    flat = jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves]) if leaves else jnp.zeros((0,), jnp.float32)

    def unflatten(vec: jax.Array):
        out, off = [], 0
        for shape, size, dtype in zip(shapes, sizes, dtypes):
            out.append(vec[off : off + size].reshape(shape).astype(dtype))
            off += size
        return jax.tree_util.tree_unflatten(treedef, out)

    return flat, unflatten


def pytree_size(tree) -> int:
    return sum(int(np.prod(l.shape)) if l.shape else 1 for l in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
# Gram-based relationship math (pure; works on sharded or local arrays)
# ---------------------------------------------------------------------------
def cossim_from_gram(gram: jax.Array) -> jax.Array:
    """(P, P) cosine-similarity matrix from a Gram matrix."""
    norms = jnp.sqrt(jnp.maximum(jnp.diag(gram), _EPS))
    return gram / (norms[:, None] * norms[None, :])


def conflict_pairs_from_gram(gram: jax.Array) -> jax.Array:
    """Algorithm 3's ordered conflicting-pair count from U Uᵀ.

    Integer-valued fp32 scalar (exact up to 2²⁴ pairs); the callers derive
    the per-client average as ``pairs / p`` instead of round-tripping it
    through a lossy normalize/denormalize.
    """
    p = gram.shape[0]
    cos = cossim_from_gram(gram)
    mask = 1.0 - jnp.eye(p, dtype=cos.dtype)
    return jnp.sum((cos < 0.0).astype(jnp.float32) * mask)


def conflict_degree_from_gram(gram: jax.Array) -> jax.Array:
    """Algorithm 3's average conflicting peers per client, from U Uᵀ."""
    return conflict_pairs_from_gram(gram) / gram.shape[0]


def masked_conflict_pairs_from_gram(gram: jax.Array, valid: jax.Array) -> jax.Array:
    """:func:`conflict_pairs_from_gram` restricted to rows where ``valid``.

    The mesh-bound async server counts Alg. 3 conflicts over its fixed-shape
    (K, D) arrival buffer; only pairs whose BOTH rows landed this round are
    counted.  With ``valid`` all-True the pair mask multiplies by exactly
    1.0, so the count is bitwise :func:`conflict_pairs_from_gram` — the τ=0
    equivalence the async harness pins.
    """
    k = gram.shape[0]
    cos = cossim_from_gram(gram)
    vm = valid.astype(cos.dtype)
    mask = vm[:, None] * vm[None, :] * (1.0 - jnp.eye(k, dtype=cos.dtype))
    return jnp.sum((cos < 0.0).astype(jnp.float32) * mask)


def async_relationship_from_dots(
    uu: jax.Array,       # ⟨u_p, u_q⟩            (fresh p, stored q)
    qq: jax.Array,       # ⟨u_q, u_q⟩
    rq: jax.Array,       # ⟨w−a_q, u_q⟩
    rr: jax.Array,       # ⟨w−a_q, w−a_q⟩
    ru: jax.Array,       # ⟨w−a_q, u_p⟩
    pp: jax.Array,       # ⟨u_p, u_p⟩
) -> jax.Array:
    """Eq. 6 from inner products only (no O(D) vectors materialized).

    Let r = w−a_q (before) and r' = r+u_p (after).  Then
    ``orthdist² = ||·||² − ⟨·, u_q⟩²/||u_q||²`` for each of r, r'.
    """
    qq = jnp.maximum(qq, _EPS)
    d_o2 = jnp.maximum(rr - rq * rq / qq, 0.0)
    rpq = rq + uu                      # ⟨r', u_q⟩
    rr2 = rr + 2.0 * ru + pp           # ||r'||²
    d_p2 = jnp.maximum(rr2 - rpq * rpq / qq, 0.0)
    ratio = jnp.sqrt(d_p2 / jnp.maximum(d_o2, _EPS))
    return jnp.clip(1.0 - ratio, -1.0, 1.0)


# ---------------------------------------------------------------------------
# Sharded reductions
# ---------------------------------------------------------------------------
# Every reduction below resolves its program through an ``lru_cache`` keyed by
# (mesh, axes): building a fresh ``shard_map`` per call would re-trace and
# re-dispatch the collective program every round (the dominant cost of the
# sharded loop engine before PR 5).  The cached callables are jitted, so
# repeat calls with the same shapes reuse the compiled executable, and calling
# them inside an outer trace (the compiled round chunks) simply inlines them.
def mesh_axes_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    """Total number of D-shards: the product of the mesh sizes of ``axes``."""
    return int(np.prod([mesh.shape[a] for a in axes]))


def pad_dim(d: int, multiple: int) -> int:
    """Smallest multiple of ``multiple`` that is >= d."""
    return -(-int(d) // int(multiple)) * int(multiple)


def _pad_last(x: jax.Array, to: int) -> jax.Array:
    """Zero-pad the trailing (D) axis to ``to`` columns.

    Exact for every reduction here: padded columns contribute 0 to all inner
    products and the padded tail of an aggregated vector is never read.
    """
    d = x.shape[-1]
    if d == to:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, to - d)]
    return jnp.pad(x, widths)


@functools.lru_cache(maxsize=None)
def _gram_program(mesh: Mesh, axes: Tuple[str, ...]):
    n_shards = mesh_axes_size(mesh, axes)

    def local(u_shard):
        g = kops.gram(u_shard)
        return jax.lax.psum(g, axes)

    sm = _shard_map(local, mesh, P(None, axes), P(None, None))

    def run(u):
        return sm(_pad_last(u, pad_dim(u.shape[-1], n_shards)))

    return jax.jit(run)


def sharded_gram(u: jax.Array, mesh: Mesh, axes: Tuple[str, ...]) -> jax.Array:
    """``u @ u.T`` for (P, D) with D sharded over ``axes``; result replicated.

    D is zero-padded to a multiple of the shard count, so ragged dims work.
    """
    return _gram_program(mesh, tuple(axes))(u)


@functools.lru_cache(maxsize=None)
def _cross_gram_program(mesh: Mesh, axes: Tuple[str, ...]):
    n_shards = mesh_axes_size(mesh, axes)

    def local(u_shard, v_shard):
        g = kops.cross_gram(u_shard, v_shard)
        return jax.lax.psum(g, axes)

    sm = _shard_map(local, mesh, (P(None, axes), P(None, axes)), P(None, None))

    def run(u, v):
        d_pad = pad_dim(u.shape[-1], n_shards)
        return sm(_pad_last(u, d_pad), _pad_last(v, d_pad))

    return jax.jit(run)


def sharded_cross_gram(u: jax.Array, v: jax.Array, mesh: Mesh, axes: Tuple[str, ...]) -> jax.Array:
    return _cross_gram_program(mesh, tuple(axes))(u, v)


@functools.lru_cache(maxsize=None)
def _aggregate_program(mesh: Mesh, axes: Tuple[str, ...]):
    n_shards = mesh_axes_size(mesh, axes)

    def local(w_shard, u_shard, p_full):
        return kops.weighted_aggregate(w_shard, u_shard, p_full)

    sm = _shard_map(local, mesh, (P(axes), P(None, axes), P(None)), P(axes))

    def run(w, updates, weights):
        d = w.shape[-1]
        d_pad = pad_dim(d, n_shards)
        out = sm(_pad_last(w, d_pad), _pad_last(updates, d_pad), weights)
        return out if d == d_pad else out[:d]

    return jax.jit(run)


def sharded_aggregate(
    w: jax.Array, updates: jax.Array, weights: jax.Array, mesh: Mesh, axes: Tuple[str, ...]
) -> jax.Array:
    """Eq. 4 on D-sharded vectors; no cross-shard traffic (weights replicated)."""
    return _aggregate_program(mesh, tuple(axes))(w, updates, weights)


@functools.lru_cache(maxsize=None)
def _relationship_dots_program(mesh: Mesh, axes: Tuple[str, ...]):
    n_shards = mesh_axes_size(mesh, axes)

    def local(u_s, w_s, v_s, a_s):
        dots = (
            kops.cross_gram(u_s, v_s),        # (K, M) ⟨u_k, v_j⟩
            kops.cross_gram(u_s, a_s),        # (K, M) ⟨u_k, a_j⟩
            u_s @ w_s,                        # (K,)   ⟨u_k, w⟩
            v_s @ w_s,                        # (M,)   ⟨v_j, w⟩
            a_s @ w_s,                        # (M,)   ⟨a_j, w⟩
            jnp.sum(v_s * v_s, axis=1),       # (M,)   ‖v_j‖²
            jnp.sum(a_s * v_s, axis=1),       # (M,)   ⟨a_j, v_j⟩
            jnp.sum(a_s * a_s, axis=1),       # (M,)   ‖a_j‖²
            jnp.vdot(w_s, w_s),               #        ‖w‖²
        )
        return tuple(jax.lax.psum(x, axes) for x in dots)

    in_specs = (P(None, axes), P(axes), P(None, axes), P(None, axes))
    out_specs = (
        P(None, None), P(None, None), P(None), P(None), P(None),
        P(None), P(None), P(None), P(),
    )
    sm = _shard_map(local, mesh, in_specs, out_specs)

    def run(u, w, v, a):
        d_pad = pad_dim(u.shape[-1], n_shards)
        return sm(
            _pad_last(u, d_pad), _pad_last(w, d_pad),
            _pad_last(v, d_pad), _pad_last(a, d_pad),
        )

    return jax.jit(run)


def sharded_relationship_dots(
    u: jax.Array,      # (K, D) fresh updates
    w: jax.Array,      # (D,)   global model
    v: jax.Array,      # (M, D) update map V
    a: jax.Array,      # (M, D) anchor map A
    mesh: Mesh,
    axes: Tuple[str, ...],
):
    """Every inner product ``relationship_block`` needs, in ONE shard_map.

    Per shard: two Pallas cross-Gram contractions plus O(M) vector dots; one
    fused psum reduces all nine results across the D-shards.  Returns the
    replicated tuple ``(uv, ua, uw, vw, aw, vv, av, aa, ww)`` — see
    ``repro.core.relationship.rows_from_relationship_dots`` for the meaning
    of each.
    """
    return _relationship_dots_program(mesh, tuple(axes))(u, w, v, a)


# ---------------------------------------------------------------------------
# The paper-technique step for the dry-run / §Perf
# ---------------------------------------------------------------------------
def flrce_round_step(
    w: jax.Array,          # (D,) global model, D-sharded
    updates: jax.Array,    # (P, D) fresh client updates, D-sharded
    anchors_dot: jax.Array,    # (P,) placeholder for stored-map dots (see below)
    weights: jax.Array,    # (P,) aggregation weights p_k
):
    """One FLrce server round on sharded vectors (Eq. 4 + Eq. 5 + Alg. 3).

    Returns (new_w, cossim matrix, conflict degree).  This is the function the
    dry-run lowers to prove the paper's server math shards: a D-sharded Gram
    contraction (reduce over D axes), a fused aggregation, and tiny replicated
    postprocessing.  ``anchors_dot`` keeps the signature stable for the async
    extension without forcing the (M, D) stored maps into the dry-run.
    """
    u32 = updates.astype(jnp.float32)
    gram = u32 @ u32.T                         # GSPMD: local matmul + all-reduce
    cos = cossim_from_gram(gram)
    conflicts = conflict_degree_from_gram(gram)
    new_w = w.astype(jnp.float32) + weights.astype(jnp.float32) @ u32
    del anchors_dot
    return new_w, cos, conflicts
