"""Client stores for the compiled (scan) round driver: device-resident + host-paged.

The loop drivers rebuild and upload a fresh ``(P, S, B, *feat)`` cohort plan
every round — O(cohort bytes) of host work and host→device traffic per round.
The scan driver instead uploads every client's shard ONCE as stacked
``(M, N_max, …)`` tensors and, per chunk of rounds, only the *batch index*
schedules (int32, ~feature_dim× smaller).  Selection then happens inside the
jitted chunk program and the round's ``(P, S, B, …)`` batches are gathered
on device from the store.

At fleet scale (M ≫ any round's cohort) the resident layout stops fitting:
:class:`HostClientStore` keeps the (M, N_max, …) universe in host memory and
:meth:`HostClientStore.page` uploads only a chunk's candidate rows — a
``(P_cand, N_max, …)`` page the chunk program indexes by *slot* (position in
the candidate set) instead of global client id.  Pages are fresh async
``device_put`` buffers, so the pipelined driver double-buffers them exactly
like :func:`place_schedule` buffers: chunk k+1's page transfers while chunk k
computes, and device memory stays O(P_cand), flat in M.

Host size accounting is int64 throughout: flattened (client, sample) row
indices live in the ``M·N_max`` space, which exceeds int32 once the fleet
passes ~2³¹ total padded samples (:func:`flat_row_index`,
:func:`validate_store_geometry`).

For the mesh-sharded chunks (``driver="scan", engine="sharded"``) the store
is laid out sharded over the mesh ``data`` axis along the client dimension
(:meth:`DeviceClientStore.shard`) and each chunk's index schedules are placed
the same way (:func:`shard_schedule`), so neither the samples nor the
schedules are ever replicated across the data shards.

Numerics contract: a schedule entry is drawn from the same per-``(t, client)``
fold-in stream the loop engines consume (``repro.fl.client.client_batch_rng``,
passed in as ``rng_for``), and padding follows ``build_cohort_plan`` exactly —
padded samples carry zero weight and padded steps zero validity, so a
gathered cohort reproduces the batched engine's math bit-for-bit up to fp32
reduction order.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.loader import bucket_steps as _bucket_steps
from repro.data.synthetic import FederatedDataset

_INT32_MAX = np.iinfo(np.int32).max


def validate_store_geometry(m: int, n_max: int) -> None:
    """Reject store shapes whose index math cannot be represented.

    Per-row sample positions must fit int32 (batch schedules are int32), and
    the flattened (client, sample) row-index space ``m * n_max`` must fit
    int64 — the product routinely exceeds int32 at fleet scale, which is why
    every host-side flat index goes through :func:`flat_row_index` (int64)
    instead of multiplying int32 sizes.
    """
    if m < 0 or n_max < 0:
        raise ValueError(f"store geometry must be non-negative, got M={m}, N_max={n_max}")
    if n_max > _INT32_MAX:
        raise ValueError(
            f"N_max={n_max} exceeds int32; batch schedules index samples in int32"
        )
    if int(m) * int(n_max) > np.iinfo(np.int64).max:
        raise ValueError(f"M·N_max={m}·{n_max} overflows int64 flat indexing")


def flat_row_index(cids: np.ndarray, pos: np.ndarray, n_max: int) -> np.ndarray:
    """Flattened (client, sample) → index into an ``(M * N_max, …)`` view.

    Always int64: with M·N_max beyond 2³¹ the int32 product silently wraps
    negative (the overflow this helper exists to prevent — see the boundary
    test in ``tests/test_paged_store.py``).
    """
    cids = np.asarray(cids, np.int64)
    pos = np.asarray(pos, np.int64)
    return cids * np.int64(n_max) + pos


@dataclasses.dataclass
class DeviceClientStore:
    """Every client's shard stacked into device tensors, padded to N_max."""

    x: jax.Array              # (M[_pad], N_max, F) float32
    y: jax.Array              # (M[_pad], N_max) int32
    sizes: jax.Array          # (M,) int32 — real samples per client
    sizes_host: np.ndarray    # int64 host copy for schedule building / the ledger

    @property
    def num_clients(self) -> int:
        # NOT x.shape[0]: a mesh-sharded store pads the client axis to the
        # data-axis size (padded rows are never selected)
        return len(self.sizes_host)

    @classmethod
    def from_dataset(
        cls, ds: FederatedDataset, *, mesh=None, data_axis: str = "data"
    ) -> "DeviceClientStore":
        """Stack every client shard into device tensors.

        With ``mesh`` the sample tensors are placed directly in the
        data-axis-sharded layout — the host NumPy staging arrays are
        ``device_put`` exactly once, never uploaded replicated first.
        """
        host = HostClientStore.from_dataset(ds)
        if mesh is None:
            x_dev, y_dev = jnp.asarray(host.x), jnp.asarray(host.y)
        else:
            x_dev, y_dev = _place_client_sharded(host.x, host.y, mesh, data_axis)
        return cls(
            x=x_dev,
            y=y_dev,
            sizes=jnp.asarray(host.sizes_host.astype(np.int32)),
            sizes_host=host.sizes_host,
        )

    def shard(self, mesh, data_axis: str = "data") -> "DeviceClientStore":
        """Re-lay an existing store out sharded over the mesh ``data`` axis.

        Bounces the sample tensors through the host; prefer
        ``from_dataset(ds, mesh=...)``, which places them sharded in one
        transfer.  Kept for stores built without a mesh in hand.
        """
        x_dev, y_dev = _place_client_sharded(
            np.asarray(self.x), np.asarray(self.y), mesh, data_axis
        )
        return dataclasses.replace(self, x=x_dev, y=y_dev)

    def gather_cohort(
        self,
        ids: jax.Array,           # (P,) traced schedule indices
        batch_idx: jax.Array,     # (M | P_cand, S, B) int32 — this round's schedule
        sample_w: jax.Array,      # (M | P_cand, S, B) float32
        step_valid: jax.Array,    # (M | P_cand, S) float32
        *,
        rows: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """Materialize the selected cohort's padded batches on device.

        Traceable (runs inside the scan body, after on-device selection).
        ``ids`` index the schedule tensors' leading axis; ``rows`` (default
        ``ids``) index the store's client axis.  They coincide for a
        full-universe store with full-universe schedules; with per-candidate
        schedules a resident store passes global client ids as ``rows`` and
        candidate-relative slots as ``ids`` (a paged store's rows ARE slots,
        so the default applies again).  Returns ``(x (P,S,B,F),
        y (P,S,B), sample_w (P,S,B), step_valid (P,S))`` — exactly a
        :class:`CohortPlan`'s arrays.
        """
        r = ids if rows is None else rows
        bi = batch_idx[ids]                              # (P, S, B)
        r = r[:, None, None]
        return self.x[r, bi], self.y[r, bi], sample_w[ids], step_valid[ids]


def _place_client_sharded(
    x: np.ndarray, y: np.ndarray, mesh, data_axis: str
) -> Tuple[jax.Array, jax.Array]:
    """Pad the client axis to the ``data``-axis size and ``device_put`` the
    sample tensors split along it — each data shard holds only its
    M/n_data slice of the O(M·N_max·feat) store (a padded row holds no real
    samples and no id ever selects it)."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core.distributed import pad_dim

    n_data = mesh.shape[data_axis]
    m = x.shape[0]
    m_pad = pad_dim(m, n_data)
    if m_pad != m:
        x = np.concatenate([x, np.zeros((m_pad - m, *x.shape[1:]), x.dtype)])
        y = np.concatenate([y, np.zeros((m_pad - m, *y.shape[1:]), y.dtype)])
    row = lambda a: NamedSharding(
        mesh, PartitionSpec(data_axis, *([None] * (a.ndim - 1)))
    )
    return jax.device_put(x, row(x)), jax.device_put(y, row(y))


@dataclasses.dataclass
class HostClientStore:
    """The (M, N_max, …) client universe in HOST memory, paged on demand.

    The scan driver's fleet-scale layout (``client_store="paged"``): the
    stacked sample tensors never reach the device whole.  Per chunk the
    driver computes a candidate set (the union of the chunk's cohorts, or a
    device-selection candidate superset), calls :meth:`page`, and the chunk
    program sees only that ``(P_cand, N_max, …)`` slice — slot-indexed, with
    ``ids = cand[slots]`` recovering global client ids inside the trace.
    Device memory is therefore O(P_cand) regardless of M; at pipeline depth
    2 at most two pages are live at once.
    """

    x: np.ndarray             # (M, N_max, F) float32, features flattened
    y: np.ndarray             # (M, N_max) int32
    sizes_host: np.ndarray    # (M,) int64 — real samples per client

    @property
    def num_clients(self) -> int:
        return len(self.sizes_host)

    @property
    def nbytes(self) -> int:
        return self.x.nbytes + self.y.nbytes

    @classmethod
    def from_dataset(cls, ds: FederatedDataset) -> "HostClientStore":
        """Stack every client shard into padded host tensors.

        One vectorized scatter instead of a per-client Python loop: all
        sample rows land via a single int64 flat-index assignment
        (:func:`flat_row_index`), so construction is O(total samples) NumPy
        work even at M ≥ 10⁵ clients.

        Each sample's features are stored flattened to one trailing axis of
        F values (the compiled chunk restores the dataset's feature shape
        as it loads a batch): a TPU
        tiles the two minor dims of an array by (8, 128), so an image
        store's (W, C) = (32, 3) minor dims would take 4x their bytes in
        HBM, and so would every batch gathered from it.
        """
        sizes = ds.client_sizes().astype(np.int64)
        m = len(ds.client_indices)
        n_max = max(1, int(sizes.max()) if m else 1)
        validate_store_geometry(m, n_max)
        feat = int(np.prod(ds.x.shape[1:]))
        x = np.zeros((m, n_max, feat), np.float32)
        y = np.zeros((m, n_max), np.int32)
        if m and sizes.sum():
            cat = np.concatenate(
                [np.asarray(ix, np.int64) for ix in ds.client_indices]
            )
            rows = np.repeat(np.arange(m, dtype=np.int64), sizes)
            starts = np.cumsum(sizes) - sizes
            pos = np.arange(int(sizes.sum()), dtype=np.int64) - np.repeat(starts, sizes)
            flat = flat_row_index(rows, pos, n_max)
            x.reshape(m * n_max, feat)[flat] = ds.x[cat].reshape(len(cat), feat)
            y.reshape(m * n_max)[flat] = ds.y[cat]
        return cls(x=x, y=y, sizes_host=sizes)

    def page(
        self, cand: np.ndarray, mesh=None, data_axis: str = "data"
    ) -> DeviceClientStore:
        """Upload the candidate rows as a fresh slot-indexed device page.

        ``cand`` is the chunk's (P_cand,) global-client-id candidate array
        (host); row j of the page is client ``cand[j]``, so the chunk program
        gathers by slot.  Every call allocates FRESH async ``device_put``
        buffers — the same double-buffering discipline as
        :func:`place_schedule`: chunk k+1's page transfers over while chunk k
        executes and is freed when its plan is dropped.  With ``mesh`` the
        page rows are placed data-axis-sharded like a resident store.
        """
        cand = np.asarray(cand, np.int64)
        px, py = self.x[cand], self.y[cand]
        sizes = self.sizes_host[cand]
        if mesh is None:
            x_dev, y_dev = jax.device_put(px), jax.device_put(py)
        else:
            x_dev, y_dev = _place_client_sharded(px, py, mesh, data_axis)
        return DeviceClientStore(
            x=x_dev,
            y=y_dev,
            sizes=jnp.asarray(sizes.astype(np.int32)),
            sizes_host=sizes,
        )


@dataclasses.dataclass
class ChunkSchedule:
    """Host-built batch schedules for a chunk of rounds [t0, t0 + R).

    Index tensors only — the samples themselves never leave the client store.
    The client axis is the chunk's CANDIDATE axis: column j schedules the
    chunk's j-th candidate client (``client_ids[j]`` of
    :func:`build_chunk_schedule`; the full universe when ``client_ids`` is
    None).  Host bytes per chunk are therefore O(R · P_cand · S · B), not
    O(R · M · S · B) — a round's slice is gathered by candidate-relative
    slot inside the chunk program.
    """

    t0: int
    batch_idx: np.ndarray     # (R, P_cand, S, B) int32 — indices into a store row
    sample_w: np.ndarray      # (R, P_cand, S, B) float32: 1 = real sample, 0 = pad
    step_valid: np.ndarray    # (R, P_cand, S) float32: 1 = real step, 0 = pad

    @property
    def num_rounds(self) -> int:
        return self.batch_idx.shape[0]

    @property
    def num_steps(self) -> int:
        return self.batch_idx.shape[2]

    @property
    def nbytes(self) -> int:
        """Host bytes this chunk's schedules occupy (regression-tested to be
        O(P_cand), not O(M))."""
        return self.batch_idx.nbytes + self.sample_w.nbytes + self.step_valid.nbytes


def shard_schedule(
    sched: ChunkSchedule, mesh, data_axis: str = "data"
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Place a chunk's index tensors sharded over the mesh ``data`` axis.

    The client axis is zero-padded to the axis size (matching
    :meth:`DeviceClientStore.shard`; a padded client's schedule is
    all-invalid and never gathered) so each data shard receives only its
    slice of the (R, M, S, B) tensors instead of a full replica.  Returns
    device ``(batch_idx, sample_w, step_valid)``.
    """
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core.distributed import pad_dim

    n_data = mesh.shape[data_axis]
    m = sched.batch_idx.shape[1]
    m_pad = pad_dim(m, n_data)

    def place(a: np.ndarray) -> jax.Array:
        if m_pad != m:
            widths = [(0, 0), (0, m_pad - m)] + [(0, 0)] * (a.ndim - 2)
            a = np.pad(a, widths)
        spec = PartitionSpec(None, data_axis, *([None] * (a.ndim - 2)))
        return jax.device_put(a, NamedSharding(mesh, spec))

    return place(sched.batch_idx), place(sched.sample_w), place(sched.step_valid)


def place_schedule(
    sched: ChunkSchedule, mesh=None, data_axis: str = "data"
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Place a chunk's index tensors on device, mesh-aware.

    With ``mesh`` this is :func:`shard_schedule`; without, a plain async
    ``jax.device_put`` of the three host arrays.  Either way each call
    allocates FRESH device buffers — the pipelined chunk driver relies on
    that for double-buffering: chunk k+1's transfers (dispatched while chunk
    k executes) can never alias schedule tensors an in-flight chunk still
    reads, and the copies themselves are asynchronous, so building+placing
    the next chunk overlaps the current chunk's device compute.
    """
    if mesh is not None:
        return shard_schedule(sched, mesh, data_axis)
    return (
        jax.device_put(sched.batch_idx),
        jax.device_put(sched.sample_w),
        jax.device_put(sched.step_valid),
    )


# ---------------------------------------------------------------------------
# Chunk schedule building (host)
# ---------------------------------------------------------------------------
# Permutation memo for repeated builds: a (t, cid) schedule is a pure
# function of (rng stream, n, epochs, batch_size), and the stream is keyed by
# the caller-provided ``cache_key`` (the job seed).  Benchmarks and
# equivalence harnesses build the same chunk schedules several times per
# process (batched vs scan legs, chunk-alignment sweeps); the memo turns the
# repeat draws into array reuse.  Bounded FIFO: a single long job inserts
# strictly-increasing round keys it never reads back, so without a cap the
# memo would grow O(rounds · clients) — eviction keeps the repeat-build win
# (which only needs the most recent jobs' entries) at constant memory.
_SCHEDULE_MEMO: Dict[tuple, Tuple[np.ndarray, np.ndarray]] = {}
_SCHEDULE_MEMO_MAX = 4096


def clear_schedule_memo() -> None:
    _SCHEDULE_MEMO.clear()


def _memo_put(key: tuple, val: Tuple[np.ndarray, np.ndarray]) -> None:
    while len(_SCHEDULE_MEMO) >= _SCHEDULE_MEMO_MAX:
        _SCHEDULE_MEMO.pop(next(iter(_SCHEDULE_MEMO)))   # FIFO (dict order)
    _SCHEDULE_MEMO[key] = val


def _client_schedule(
    n: int,
    e: int,
    batch_size: int,
    rng_k: np.random.Generator,
) -> Tuple[np.ndarray, np.ndarray]:
    """One (t, client) schedule: ``(idx (s_k, B) int32, w (s_k, B) f32)``.

    Vectorized form of the reference per-batch loop: the e permutation draws
    stay sequential on the client's fold-in stream (that order is the
    numerics contract), but batching is a pad + reshape — only the last
    batch of an epoch is partial, so padding the flattened epoch tail is
    bitwise-identical to the old per-``start`` slicing.
    """
    nb = -(-n // batch_size) if n else 0
    s_k = e * nb
    if s_k == 0:
        return (
            np.zeros((0, batch_size), np.int32),
            np.zeros((0, batch_size), np.float32),
        )
    perms = np.stack([rng_k.permutation(n) for _ in range(e)])        # (e, n)
    pad = nb * batch_size - n
    idx = np.pad(perms, ((0, 0), (0, pad))).reshape(s_k, batch_size)
    w = np.pad(np.ones((e, n), np.float32), ((0, 0), (0, pad)))
    return idx.astype(np.int32), w.reshape(s_k, batch_size)


def build_chunk_schedule(
    sizes: np.ndarray,                       # (P_cand,) samples per candidate
    epochs: np.ndarray,                      # (R, P_cand) local epochs per (round, candidate)
    batch_size: int,
    t0: int,
    rng_for: Callable[[int, int], np.random.Generator],
    *,
    bucket_steps: bool = True,
    cache_key: Optional[int] = None,
    client_ids: Optional[np.ndarray] = None,
) -> ChunkSchedule:
    """Draw every (round, candidate) batch schedule for a chunk of rounds.

    ``rng_for(t, cid)`` must return the same independent stream the loop
    engines use (``client_batch_rng``); each stream is consumed exactly like
    ``build_cohort_plan`` consumes it — one ``permutation(n)`` per epoch, in
    epoch order — so the scan driver's schedules are placement- and
    driver-independent.  The step axis is sized to the chunk-wide maximum and
    bucketed to a power of two so the jitted chunk program retraces per size
    bucket, not per chunk.

    ``client_ids`` maps schedule column → GLOBAL client id (default: column
    j is client j, the full-universe layout).  Passing the chunk's candidate
    set builds per-cohort ``(R, P_cand, S, B)`` schedules whose columns draw
    from the candidates' own fold-in streams — O(P_cand) host bytes and
    draws per chunk instead of O(M), bitwise-identical per client to the
    dense build (the stream is keyed by the global id, not the column).

    ``cache_key`` (the job's batch seed) enables the permutation memo: when
    set, each ``(cache_key, t, cid, n, e, batch_size)`` draw is computed once
    per process and reused — ``rng_for`` is not even invoked on a hit, which
    is exact because the stream is a pure function of ``(seed, t, cid)``.
    Memo keys use the global id, so dense and per-cohort builds share hits.
    """
    sizes = np.asarray(sizes)
    epochs = np.asarray(epochs)
    r_rounds, m = epochs.shape
    if len(sizes) != m:
        raise ValueError(f"sizes has {len(sizes)} clients, epochs has {m}")
    if client_ids is not None and len(client_ids) != m:
        raise ValueError(
            f"client_ids has {len(client_ids)} entries, epochs has {m} columns"
        )
    per_round = []
    s_max = 1
    for r in range(r_rounds):
        t = t0 + r
        per_client = []
        for col in range(m):
            cid = int(client_ids[col]) if client_ids is not None else col
            n = int(sizes[col])
            e = max(1, int(epochs[r, col]))
            memo_key = (cache_key, t, cid, n, e, batch_size)
            if cache_key is not None and memo_key in _SCHEDULE_MEMO:
                idx, w = _SCHEDULE_MEMO[memo_key]
            else:
                idx, w = _client_schedule(n, e, batch_size, rng_for(t, cid))
                if cache_key is not None:
                    _memo_put(memo_key, (idx, w))
            per_client.append((idx, w, idx.shape[0]))
            s_max = max(s_max, idx.shape[0])
        per_round.append(per_client)

    s_pad = _bucket_steps(s_max) if bucket_steps else s_max
    batch_idx = np.zeros((r_rounds, m, s_pad, batch_size), np.int32)
    sample_w = np.zeros((r_rounds, m, s_pad, batch_size), np.float32)
    step_valid = np.zeros((r_rounds, m, s_pad), np.float32)
    for r, per_client in enumerate(per_round):
        for cid, (idx, w, s_k) in enumerate(per_client):
            batch_idx[r, cid, :s_k] = idx
            sample_w[r, cid, :s_k] = w
            step_valid[r, cid, :s_k] = 1.0
    return ChunkSchedule(
        t0=t0, batch_idx=batch_idx, sample_w=sample_w, step_valid=step_valid
    )
