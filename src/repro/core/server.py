"""FLrce server (paper Algorithm 4) — stateful orchestration of one FL job.

The server operates on *flattened* update vectors; the FL engine
(`repro.fl.rounds`) flattens/unflattens model pytrees at the boundary.
State carried across rounds (Table 1):

* ``omega`` (M, M) — relationship map Ω
* ``heuristic`` (M,) — H, row-sums of Ω (Eq. 7)
* ``updates`` (M, D) — V, each client's latest update
* ``anchors`` (M, D) — global model at each client's last active round
  (needed to anchor the orthdist ray; see core.relationship)
* ``last_round`` (M,) — R, each client's last active round (-1 = never)

**Sketched V/A** (``va_rows=K < M``): at fleet scale the (M, D) maps are the
dominant server allocation, yet only recently-active clients' rows are ever
read fresh.  The sketch keeps K LRU-allocated rows (``va_owner`` maps sketch
row → client, ``va_slot`` client → row, -1 = none); a client whose row was
evicted is treated as never seen (its Ω entries freeze at their last value,
exactly the exact path's unseen handling).  With ``va_rows=None`` or
``va_rows >= M`` the maps are exact and every result is bitwise the
unsketched server's — the equivalence switch the scan/paged drivers rely on.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import early_stopping, heuristics, relationship, selection


@dataclasses.dataclass
class FLrceState:
    t: int
    omega: jax.Array        # (M, M)
    heuristic: jax.Array    # (M,)
    updates: jax.Array      # (M | K, D) — K sketch rows when va_rows is set
    anchors: jax.Array      # (M | K, D)
    last_round: jax.Array   # (M,) int32
    stopped: bool = False
    stop_round: Optional[int] = None
    last_conflicts: float = 0.0
    va_owner: Optional[jax.Array] = None   # (K,) sketch row → client id; -1 empty
    va_slot: Optional[jax.Array] = None    # (M,) client id → sketch row; -1 none


def init_state(
    num_clients: int, dim: int, va_rows: Optional[int] = None
) -> FLrceState:
    m = num_clients
    k = m if va_rows is None else min(int(va_rows), m)
    sketched = k < m
    return FLrceState(
        t=0,
        omega=jnp.zeros((m, m), jnp.float32),
        heuristic=jnp.zeros((m,), jnp.float32),
        updates=jnp.zeros((k, dim), jnp.float32),
        anchors=jnp.zeros((k, dim), jnp.float32),
        last_round=jnp.full((m,), -1, jnp.int32),
        va_owner=jnp.full((k,), -1, jnp.int32) if sketched else None,
        va_slot=jnp.full((m,), -1, jnp.int32) if sketched else None,
    )


def sketch_assign_rows(
    va_owner: jax.Array,      # (K,) sketch row → owning client id; -1 empty
    va_slot: jax.Array,       # (M,) client id → sketch row; -1 none
    last_round: jax.Array,    # (M,) int32 — LRU key (BEFORE this round's write)
    ids: jax.Array,           # (P,) distinct selected client ids
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Assign a sketch row to every selected client — pure and traceable.

    Clients that already own a row keep it; the rest take rows in eviction
    order: empty rows first, then least-recently-active owners (stable, so
    ties break by row index — deterministic across drivers).  Rows owned by
    members of the current cohort are never evicted, which is always
    satisfiable because K ≥ P is validated at server construction.  Returns
    ``(va_owner', va_slot', slots)`` with ``slots[i]`` the row for ``ids[i]``.
    """
    k = va_owner.shape[0]
    m = va_slot.shape[0]
    ids = ids.astype(jnp.int32)
    existing = va_slot[ids]                              # (P,) row or -1
    has = existing >= 0
    # rows owned by this cohort are pinned (scatter index k drops out; -1
    # would WRAP under jnp indexing, hence the explicit out-of-range remap)
    pinned = (
        jnp.zeros((k,), bool)
        .at[jnp.where(has, existing, k)]
        .set(True, mode="drop")
    )
    owner_ok = va_owner >= 0
    owner_last = jnp.where(owner_ok, last_round[jnp.maximum(va_owner, 0)], -2)
    evict_key = jnp.where(pinned, jnp.iinfo(jnp.int32).max, owner_last)
    order = jnp.argsort(evict_key, stable=True)          # empties, then LRU
    need = jnp.logical_not(has)
    rank = jnp.cumsum(need.astype(jnp.int32)) - 1        # position among needy
    fresh = order[jnp.maximum(rank, 0)]
    slots = jnp.where(has, existing, fresh).astype(jnp.int32)
    # clear the evicted owners' back-pointers before writing the new ones
    old_owner = va_owner[slots]
    stale = jnp.logical_and(need, old_owner >= 0)
    va_slot = va_slot.at[jnp.where(stale, jnp.maximum(old_owner, 0), m)].set(
        -1, mode="drop"
    )
    va_slot = va_slot.at[ids].set(slots)
    va_owner = va_owner.at[slots].set(ids)
    return va_owner, va_slot, slots


class FLrceServer:
    """Relationship-based selection + early stopping, over flattened updates."""

    def __init__(
        self,
        num_clients: int,
        dim: int,
        clients_per_round: int,
        es_threshold: float,
        explore_decay: float = 0.98,
        seed: int = 0,
        va_rows: Optional[int] = None,
    ):
        self.m = num_clients
        self.dim = dim
        self.p = clients_per_round
        self.psi = es_threshold
        self.decay = explore_decay
        self._rng = jax.random.PRNGKey(seed)
        # va_rows=K < M sketches the (M, D) V/A maps down to K LRU rows;
        # None (or K >= M) is the exact path — bitwise the historical server
        self.va_rows = None if va_rows is None else int(va_rows)
        if self.va_rows is not None and self.va_rows < clients_per_round:
            raise ValueError(
                f"va_rows={va_rows} must be >= clients_per_round="
                f"{clients_per_round}: every selected client needs a sketch row"
            )
        self.state = init_state(num_clients, dim, va_rows=self.va_rows)
        self._last_exploit = False
        # mesh-sharded storage: set by bind_mesh (None ⇒ single-device maps)
        self.mesh = None
        self.mesh_axes: Tuple[str, ...] = ()
        self.dim_pad = dim

    @property
    def sketched(self) -> bool:
        return self.state.va_owner is not None

    # -- optional mesh-sharded storage ---------------------------------------
    def bind_mesh(self, mesh, axes: Tuple[str, ...] = ("data", "model")) -> None:
        """Move the O(D) maps (V, A) onto a device mesh, D-sharded over ``axes``.

        From here on ``ingest`` reduces its inner products through ONE fused
        shard_map (``sharded_relationship_dots``) and ``check_early_stop``
        computes Alg. 3 from a ``sharded_gram`` — the (P, D)/(M, D) buffers are
        never replicated.  The flat dim is zero-padded to a multiple of the
        shard count, which is exact for every inner product.
        """
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.core.distributed import mesh_axes_size, pad_dim

        if self.sketched:
            raise ValueError(
                "sketched V/A maps (va_rows < M) are single-device for now; "
                "run without a mesh or with va_rows=None"
            )
        self.mesh = mesh
        self.mesh_axes = tuple(axes)
        self.dim_pad = pad_dim(self.dim, mesh_axes_size(mesh, self.mesh_axes))
        shard = NamedSharding(mesh, PartitionSpec(None, self.mesh_axes))
        st = self.state
        pad = self.dim_pad - st.updates.shape[1]
        self.state = dataclasses.replace(
            st,
            updates=jax.device_put(jnp.pad(st.updates, ((0, 0), (0, pad))), shard),
            anchors=jax.device_put(jnp.pad(st.anchors, ((0, 0), (0, pad))), shard),
        )

    def _shard_cols(self, x: jax.Array) -> jax.Array:
        """Pad a (…, D) buffer to dim_pad and lay it out D-sharded."""
        from jax.sharding import NamedSharding, PartitionSpec

        pad = self.dim_pad - x.shape[-1]
        if pad:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
        spec = PartitionSpec(*([None] * (x.ndim - 1)), self.mesh_axes)
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    # -- Alg. 4 line 5: client selection ------------------------------------
    def select(self) -> np.ndarray:
        self._rng, sub = jax.random.split(self._rng)
        ids, exploited = selection.select_clients(
            sub, self.state.heuristic, self.state.t, self.p, self.decay
        )
        self._last_exploit = exploited
        return np.asarray(ids)

    @property
    def last_round_was_exploit(self) -> bool:
        return self._last_exploit

    # -- Alg. 4 lines 9-19: ingest updates, refresh Ω and H ------------------
    def ingest(
        self,
        w_t: jax.Array,
        client_ids: Sequence[int],
        client_updates: jax.Array,  # (P, D)
    ) -> None:
        st = self.state
        t = st.t
        ids = np.asarray(client_ids)
        w32 = w_t.astype(jnp.float32)
        u32 = client_updates.astype(jnp.float32)
        if self.mesh is not None:
            # D-sharded storage: pad + lay out the fresh buffers on the mesh
            w32 = self._shard_cols(w32)
            u32 = self._shard_cols(u32)
        # Alg. 4 writes V/A/R first (line 10), then models relationships, so a
        # pair selected in the same round is compared synchronously.
        ids_dev = jnp.asarray(ids)
        if self.sketched:
            va_owner, va_slot, slots = sketch_assign_rows(
                st.va_owner, st.va_slot, st.last_round, ids_dev
            )
            updates = st.updates.at[slots].set(u32)
            anchors = st.anchors.at[slots].set(w32[None, :])
            last_round = st.last_round.at[ids].set(t)
            eff_last = jnp.where(va_slot >= 0, last_round, -1)
            rows = relationship.sketched_relationship_block(
                ids_dev, u32, w32, updates, anchors, va_owner, eff_last, t,
                st.omega[ids_dev],
            )
        else:
            va_owner, va_slot = st.va_owner, st.va_slot
            updates = st.updates.at[ids].set(u32)
            anchors = st.anchors.at[ids].set(w32[None, :])
            last_round = st.last_round.at[ids].set(t)

            # All P fresh Ω rows in one fused Gram-kernel pass (no per-client
            # Python loop; each row only depends on its own previous row, so
            # the block is exactly the stacked per-row recurrence).  Mesh-
            # bound servers reduce the same inner products across the D-shards.
            if self.mesh is not None:
                rows = relationship.sharded_relationship_block(
                    ids_dev, u32, w32, updates, anchors, last_round, t,
                    st.omega[ids_dev], mesh=self.mesh, axes=self.mesh_axes,
                )
            else:
                rows = relationship.relationship_block(
                    ids_dev, u32, w32, updates, anchors, last_round, t,
                    st.omega[ids_dev],
                )
        omega = st.omega.at[ids_dev].set(rows)
        heuristic = heuristics.update_heuristic_rows(st.heuristic, omega, ids_dev)
        self.state = dataclasses.replace(
            st,
            omega=omega,
            heuristic=heuristic,
            updates=updates,
            anchors=anchors,
            last_round=last_round,
            va_owner=va_owner,
            va_slot=va_slot,
        )

    # -- Alg. 4 lines 20-23: early stopping ---------------------------------
    def check_early_stop(self, selected_updates: jax.Array) -> bool:
        # explore rounds never read the Gram (Alg. 3 only fires on exploit),
        # so don't dispatch the cross-shard contraction just to drop it
        if self.mesh is not None and self._last_exploit:
            from repro.core.distributed import sharded_gram

            gram = sharded_gram(
                self._shard_cols(selected_updates.astype(jnp.float32)),
                self.mesh, self.mesh_axes,
            )
            decision = early_stopping.should_stop_from_gram(
                gram, self.psi, is_exploit_round=True
            )
        else:
            decision = early_stopping.should_stop(
                selected_updates, self.psi, is_exploit_round=self._last_exploit
            )
        st = self.state
        self.state = dataclasses.replace(
            st,
            stopped=st.stopped or decision.stop,
            stop_round=st.stop_round if st.stopped else (st.t if decision.stop else None),
            last_conflicts=decision.conflicts,
        )
        return decision.stop

    def advance_round(self) -> None:
        self.state = dataclasses.replace(self.state, t=self.state.t + 1)

    # -- functional (scan-driver) variants -----------------------------------
    # Pure, jit/scan-traceable versions of select / ingest / check_early_stop
    # operating on a device-resident carry dict instead of ``self.state``, so
    # the compiled round driver can fuse whole round chunks into one
    # ``lax.scan`` program.  ``scan_carry``/``load_scan_carry`` convert
    # between the host state and the carry at chunk boundaries.  A mesh-bound
    # server (``bind_mesh``) exports its V/A maps D-sharded and its traced
    # pieces reduce through the cached shard_map programs
    # (``sharded_relationship_dots`` / ``sharded_gram``), so the carry stays
    # mesh-resident across the whole compiled chunk.

    def scan_carry(self) -> Dict[str, jax.Array]:
        """Export the server state as a device carry (all arrays).

        Mesh-bound servers hand out the (M, D_pad) V/A maps exactly as they
        live on the mesh — D-sharded over ``mesh_axes`` — and the O(M)/O(M²)
        maps replicated; the scan driver carries them through the chunk
        without ever replicating the O(M·D) state.
        """
        st = self.state
        carry = {
            "rng": self._rng,
            "omega": st.omega,
            "heuristic": st.heuristic,
            "updates": st.updates,
            "anchors": st.anchors,
            "last_round": st.last_round,
            "es_stopped": jnp.asarray(st.stopped),
            "es_stop_round": jnp.asarray(
                -1 if st.stop_round is None else st.stop_round, jnp.int32
            ),
            "conflicts": jnp.asarray(st.last_conflicts, jnp.float32),
        }
        if self.sketched:
            carry["va_owner"] = st.va_owner
            carry["va_slot"] = st.va_slot
        return carry

    def scan_select(
        self, carry: Dict[str, jax.Array], phi: jax.Array, cand: jax.Array
    ) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array]:
        """Alg. 2 on device under the candidate-set contract.

        Same key split sequence as :meth:`select`; returns candidate-relative
        ``slots`` (the scan driver recovers ids as ``cand[slots]``).  With
        ``cand = arange(M)`` the draw is bitwise :meth:`select`'s.
        """
        rng, sub = jax.random.split(carry["rng"])
        slots, exploited = selection.select_clients_device_candidates(
            sub, carry["heuristic"], cand, phi, self.p
        )
        return {**carry, "rng": rng}, slots, exploited

    # the scan forms run under named scopes (``fl/ingest``, ``fl/early_stop``):
    # their device ops carry the phase in the profiler's ``tf_op``
    @jax.named_scope("fl/ingest")
    def scan_ingest(
        self,
        carry: Dict[str, jax.Array],
        w_t: jax.Array,
        ids: jax.Array,           # (P,) traced client ids
        client_updates: jax.Array,  # (P, D)
        t: jax.Array,
    ) -> Dict[str, jax.Array]:
        """:meth:`ingest` as a pure function of the carry (traced ids/t).

        Mesh-bound servers receive ``w_t``/``client_updates`` already padded
        to ``dim_pad`` and D-sharded (the sharded chunk's round buffers) and
        reduce the nine dot groups through the cached fused shard_map, like
        the loop path's :meth:`ingest`.
        """
        w32 = w_t.astype(jnp.float32)
        u32 = client_updates.astype(jnp.float32)
        out: Dict[str, jax.Array] = {}
        if self.sketched:
            va_owner, va_slot, slots = sketch_assign_rows(
                carry["va_owner"], carry["va_slot"], carry["last_round"], ids
            )
            updates = carry["updates"].at[slots].set(u32)
            anchors = carry["anchors"].at[slots].set(w32[None, :])
            last_round = carry["last_round"].at[ids].set(t.astype(jnp.int32))
            eff_last = jnp.where(va_slot >= 0, last_round, -1)
            rows = relationship.sketched_relationship_block(
                ids, u32, w32, updates, anchors, va_owner, eff_last, t,
                carry["omega"][ids],
            )
            out["va_owner"], out["va_slot"] = va_owner, va_slot
        else:
            updates = carry["updates"].at[ids].set(u32)
            anchors = carry["anchors"].at[ids].set(w32[None, :])
            last_round = carry["last_round"].at[ids].set(t.astype(jnp.int32))
            if self.mesh is not None:
                rows = relationship.sharded_relationship_block(
                    ids, u32, w32, updates, anchors, last_round, t,
                    carry["omega"][ids], mesh=self.mesh, axes=self.mesh_axes,
                )
            else:
                rows = relationship.relationship_block(
                    ids, u32, w32, updates, anchors, last_round, t,
                    carry["omega"][ids],
                )
        omega = carry["omega"].at[ids].set(rows)
        heuristic = heuristics.update_heuristic_rows(carry["heuristic"], omega, ids)
        return {
            **carry,
            **out,
            "omega": omega,
            "heuristic": heuristic,
            "updates": updates,
            "anchors": anchors,
            "last_round": last_round,
        }

    @jax.named_scope("fl/early_stop")
    def scan_check_early_stop(
        self,
        carry: Dict[str, jax.Array],
        selected_updates: jax.Array,
        t: jax.Array,
        exploited: jax.Array,
    ) -> Tuple[Dict[str, jax.Array], jax.Array]:
        """Alg. 3 on device: same bookkeeping as :meth:`check_early_stop`.

        Returns ``(carry, stop)`` where ``stop`` is this round's decision
        (explore rounds never stop), mirroring the host path's gating.

        The stop compare happens on the exact integer pair count against a
        host-precomputed integer threshold (the smallest count whose f64
        average reaches ψ), so the decision is bitwise-identical to the host
        path's ``pairs / p >= psi`` in f64 — an on-device fp32 division
        could flip a near-threshold round.

        Mesh-bound servers count pairs from a ``sharded_gram`` — the same
        reduction the loop path's :meth:`check_early_stop` uses on exploit
        rounds — so the D-sharded (P, D_pad) buffer never gets replicated.
        """
        p = selected_updates.shape[0]
        if self.mesh is not None:
            from repro.core.distributed import (
                conflict_pairs_from_gram,
                sharded_gram,
            )

            pairs = conflict_pairs_from_gram(
                sharded_gram(selected_updates, self.mesh, self.mesh_axes)
            )
        else:
            pairs = early_stopping.conflict_pairs(selected_updates)
        avg = jnp.where(exploited, pairs / p, 0.0)
        # smallest integer n with n / p >= psi, resolved in host f64
        n0 = max(0, int(np.ceil(self.psi * p)))
        while n0 > 0 and (n0 - 1) / p >= self.psi:
            n0 -= 1
        while n0 / p < self.psi:
            n0 += 1
        dec_stop = jnp.logical_and(exploited, pairs >= jnp.float32(n0))
        prev_stopped = carry["es_stopped"]
        return {
            **carry,
            "es_stopped": jnp.logical_or(prev_stopped, dec_stop),
            "es_stop_round": jnp.where(
                prev_stopped,
                carry["es_stop_round"],
                jnp.where(dec_stop, t.astype(jnp.int32), jnp.int32(-1)),
            ),
            "conflicts": avg.astype(jnp.float32),
        }, dec_stop

    # -- async (out-of-order arrival) variants -------------------------------
    # The async scan driver holds departed updates in a fixed-shape arrival
    # buffer and lands a subset each round.  These are :meth:`scan_ingest` /
    # :meth:`scan_check_early_stop` re-derived for that regime; with every
    # row arriving in its departure round (max_staleness=0) both are bitwise
    # their synchronous counterparts — the equivalence the async harness pins.

    @jax.named_scope("fl/ingest")
    def scan_ingest_async(
        self,
        carry: Dict[str, jax.Array],
        w_t: jax.Array,             # (D,) global model at the LANDING round
        ids: jax.Array,             # (K,) arrival-buffer client ids
        t_depart: jax.Array,        # (K,) int32 departure round per row
        client_updates: jax.Array,  # (K, D) buffered updates
        anchor_rows: jax.Array,     # (K, D) global model at each row's departure
        arrived: jax.Array,         # (K,) bool — rows landing this round
    ) -> Dict[str, jax.Array]:
        """:meth:`scan_ingest` over an arrival buffer with out-of-order rows.

        V/A/R rows update **against the round the update left**: the update
        map stores the buffered update with its departure-round anchor and
        ``last_round`` records ``t_depart``, so the Eq. 5/6 freshness split in
        ``rows_from_relationship_dots`` (vector-``t`` branch) and later Eq. 6
        orthdists stay well-defined for stale arrivals.  When the same client
        lands twice in one round (a stale copy catching up alongside a fresh
        one) the freshest departure wins and the stale row is dropped from
        every scatter.  Non-arrived rows scatter to an out-of-range target
        and drop out entirely.

        With ``arrived`` all-True, distinct ids and ``t_depart == t`` (the
        max_staleness=0 chunk) every scatter target equals ``ids`` and every
        operand matches :meth:`scan_ingest`'s bitwise.
        """
        if self.sketched:
            raise ValueError(
                "async ingest requires exact V/A maps (va_rows=None); the "
                "sketched server's LRU row assignment is departure-ordered"
            )
        m = carry["last_round"].shape[0]
        w32 = w_t.astype(jnp.float32)
        u32 = client_updates.astype(jnp.float32)
        a32 = anchor_rows.astype(jnp.float32)
        ids = ids.astype(jnp.int32)
        dep32 = t_depart.astype(jnp.int32)
        # freshest-departure-wins dedup: row i loses iff some arrived row j
        # carries the same client with a strictly later departure
        same = ids[:, None] == ids[None, :]
        newer = jnp.logical_and(
            jnp.logical_and(same, arrived[None, :]),
            dep32[None, :] > dep32[:, None],
        )
        keep = jnp.logical_and(arrived, jnp.logical_not(jnp.any(newer, axis=1)))
        # losers and non-arrivals scatter out of range (index m drops; -1
        # would WRAP under jnp indexing)
        tgt = jnp.where(keep, ids, m)
        updates = carry["updates"].at[tgt].set(u32, mode="drop")
        anchors = carry["anchors"].at[tgt].set(a32, mode="drop")
        last_round = carry["last_round"].at[tgt].set(dep32, mode="drop")
        if self.mesh is not None:
            rows = relationship.sharded_relationship_block(
                ids, u32, w32, updates, anchors, last_round, dep32,
                carry["omega"][ids], mesh=self.mesh, axes=self.mesh_axes,
            )
        else:
            rows = relationship.relationship_block(
                ids, u32, w32, updates, anchors, last_round, dep32,
                carry["omega"][ids],
            )
        omega = carry["omega"].at[tgt].set(rows, mode="drop")
        heuristic = heuristics.update_heuristic_rows(carry["heuristic"], omega, tgt)
        return {
            **carry,
            "omega": omega,
            "heuristic": heuristic,
            "updates": updates,
            "anchors": anchors,
            "last_round": last_round,
        }

    @jax.named_scope("fl/early_stop")
    def scan_check_early_stop_async(
        self,
        carry: Dict[str, jax.Array],
        arrived_updates: jax.Array,  # (K, D) arrival buffer
        arrived: jax.Array,          # (K,) bool — rows landing this round
        t: jax.Array,
        exploited: jax.Array,
    ) -> Tuple[Dict[str, jax.Array], jax.Array]:
        """Alg. 3 over this round's arrivals (async counterpart of
        :meth:`scan_check_early_stop`).

        The conflict-pair count runs over the landed rows only (a pair counts
        iff BOTH rows arrived this round) and is still averaged over the
        cohort size P and compared against the same host-resolved integer
        threshold, so a full cohort of τ=0 arrivals reproduces the
        synchronous decision bitwise.  ``exploited`` is the LANDING round's
        phase: Alg. 3 only ever fires on exploit rounds, whichever round the
        arrivals departed in.
        """
        p = self.p
        if self.mesh is not None:
            from repro.core.distributed import (
                masked_conflict_pairs_from_gram,
                sharded_gram,
            )

            pairs = masked_conflict_pairs_from_gram(
                sharded_gram(arrived_updates, self.mesh, self.mesh_axes),
                arrived,
            )
        else:
            pairs = early_stopping.masked_conflict_pairs(arrived_updates, arrived)
        avg = jnp.where(exploited, pairs / p, 0.0)
        # smallest integer n with n / p >= psi, resolved in host f64
        n0 = max(0, int(np.ceil(self.psi * p)))
        while n0 > 0 and (n0 - 1) / p >= self.psi:
            n0 -= 1
        while n0 / p < self.psi:
            n0 += 1
        dec_stop = jnp.logical_and(exploited, pairs >= jnp.float32(n0))
        prev_stopped = carry["es_stopped"]
        return {
            **carry,
            "es_stopped": jnp.logical_or(prev_stopped, dec_stop),
            "es_stop_round": jnp.where(
                prev_stopped,
                carry["es_stop_round"],
                jnp.where(dec_stop, t.astype(jnp.int32), jnp.int32(-1)),
            ),
            "conflicts": avg.astype(jnp.float32),
        }, dec_stop

    def load_scan_carry(
        self, carry: Dict[str, jax.Array], t_next: int, last_exploit: bool
    ) -> None:
        """Write a chunk's final carry back into the host state (chunk flush)."""
        stop_round = int(carry["es_stop_round"])
        self.state = FLrceState(
            t=int(t_next),
            omega=carry["omega"],
            heuristic=carry["heuristic"],
            updates=carry["updates"],
            anchors=carry["anchors"],
            last_round=carry["last_round"],
            stopped=bool(carry["es_stopped"]),
            stop_round=None if stop_round < 0 else stop_round,
            last_conflicts=float(carry["conflicts"]),
            va_owner=carry.get("va_owner"),
            va_slot=carry.get("va_slot"),
        )
        self._rng = carry["rng"]
        self._last_exploit = bool(last_exploit)
