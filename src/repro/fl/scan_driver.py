"""Compiled round driver: ``lax.scan`` over whole chunks of rounds, pipelined.

The loop drivers dispatch one jitted cohort program per round and sync with
the host several times per round (plan upload, loss readback, selection,
``bool(stop)``).  In the regime the paper targets — short rounds on small
models — that dispatch overhead dominates.  This driver removes it:

* client data lives on device once (:class:`repro.data.device.DeviceClientStore`);
* a *chunk* of R rounds — select (Alg. 2) → gather batches → cohort train →
  Eq. 4 aggregate → strategy ingest/ES (Alg. 1/3) — is ONE jitted
  ``lax.scan`` program over a fully device-resident carry
  (flat model + the strategy's :class:`ScanProgram` carry);
* the carry buffers are **donated** (``donate_argnums``), so the flat model,
  the strategy's O(M·D) maps and the accuracy scalar update in place across
  chunks instead of copy-churning per chunk;
* the host syncs exactly once per chunk: it reads the stacked per-round
  outputs (ids, stop flags, accuracies, losses — O(R·P) scalars), flushes
  ``RoundRecord``s and the resource ledger, and checks the stop flag.

**Pipelined chunks** (``pipeline=True``, the default): the remaining serial
cost is the host work *between* device programs — schedule construction and
H2D upload before a chunk, record/ledger flush after it.  The driver is a
two-deep software pipeline over those phases: chunk k+1's inputs are built
and transferred while chunk k executes, chunk k+1 is dispatched (async — the
hot path never calls ``block_until_ready``) *before* the host blocks on
chunk k's outputs, and the flush of chunk k then overlaps chunk k+1's device
execution.  Because the stop decision for chunk k is only known after chunk
k+1 is already in flight, dispatch is **speculative**: the ``stopped`` flag
rides in the donated carry across chunk boundaries, so a chunk entered with
``stopped=True`` executes fully masked — its output carry is bitwise the
input carry and every round reports ``valid=False``.  The host discards a
cancelled chunk's outputs unread; records, ledger and the written-back
strategy state are bitwise-identical to the serial (``pipeline=False``)
driver, whose code path is the same loop at pipeline depth 1.

With ``mesh=`` (``run_federated(driver="scan", engine="sharded")``) the same
chunk program runs mesh-sharded: the scan body shard_maps cohort training
over the mesh ``data`` axis (the :class:`ShardedCohortTrainer` program), does
the one pad-then-all-to-all reshard to the D-sharded round layout, aggregates
through ``sharded_aggregate``, and the strategy's carry pieces reduce through
the cached sharded Gram programs (FLrce ingest via
``sharded_relationship_dots``, Alg. 3 via ``sharded_gram``).  The flat ``w``
and the (M, D_pad) maps stay D-sharded across rounds AND across chunks — the
O(D) state never leaves the mesh, and host traffic stays O(R·P) scalars per
chunk.  Pipelining composes: each chunk's index schedules are fresh
data-axis-sharded buffers (double-buffered H2D — transfers for chunk k+1
overlap chunk k's execution), and the donated D-sharded carries alternate
between the two in-flight programs exactly like the single-device path.

Numerics match the batched loop driver within fp32 tolerance: batch
schedules come from the identical ``client_batch_rng`` fold-in streams
(host-drawn per chunk, gathered on device), selection consumes the same PRNG
key sequence with the same tie-breaks (``select_clients_device``), the round
body reuses ``BatchedCohortTrainer``'s cohort program, and the strategy's
device-resident ``update_transform`` (Fedcom top-k, QuantizedFL int8) is
traced straight into the chunk.  Dropout masks and TimelyFL freeze flags are
host-materialized per chunk for the (host-precomputed) selected cohorts and
ride into the scan as stacked per-round inputs.  After an early stop fires
mid-chunk the remaining scan iterations still execute (a scan has no early
exit) but their carry writes are masked out, so the final state is the stop
round's — the wasted rounds are bounded by ``chunk_rounds`` plus, under
pipelining, one speculative chunk.

**Async rounds** (``async_rounds=AsyncConfig(...)`` via ``run_federated``):
the same chunk program runs staleness-aware rounds.  A fixed-shape ring
buffer of ``max_staleness + 1`` pending cohorts rides in the donated carry;
each round trains its cohort at departure, holds the updates back per-row
delivery delays (``AsyncPlan.delays``), and applies the staleness-weighted
Eq. 4 over whatever *landed* this round (weight ``decay(τ)``, renormalized).
Strategy bookkeeping goes through ``ScanProgram.post_round_async`` with the
flattened arrival buffer.  At ``max_staleness=0`` every update lands in its
departure round with weight exactly 1.0 and the async chunk reproduces the
synchronous chunk bitwise — records, ledger and written-back strategy state
(tests/test_async_rounds.py).  All round-index arithmetic on the buffers
goes through ``repro.fl.async_rounds.staleness_of`` (flcheck FLC007).

**Spans and counters**: every host phase of the chunk loop is one span —
a profiler ``TraceAnnotation`` named ``fl.<phase>`` (``fl.build`` and its
``fl.build.<step>`` phases, ``fl.dispatch``, ``fl.wait``, ``fl.flush``)
with the chunk's first round as its ``chunk`` stat, whose seconds also
feed ``driver_stats``; with no trace running it records nothing.  The
scan body's phases run under ``jax.named_scope("fl/<phase>")``, which
reaches each device op's ``tf_op`` in a trace and changes no op.  Each
round outputs its cohort's real local steps and its step loop's trip
count (the cohort's last real step); the flush sums them into
``driver_stats["steps_useful"]`` and ``steps_executed`` (padded cohort ×
trip count, over the rounds), beside ``steps_bucket`` (padded cohort ×
step bucket S, over the rounds: what a loop run to the bucket's end would
execute).

Strategies opt in via ``Strategy.supports_scan`` / ``scan_program()`` — FLrce
and every §4.1 baseline except PyramidFL, whose loss-driven selection/epoch
plan cannot be precomputed; the mesh-sharded chunks additionally require
``supports_sharded_scan`` (metadata-only configs, no update transform).
``run_federated`` falls back to the matching loop engine otherwise
(docs/support-matrix.md tabulates the full picture).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.compile_guard import CompileCounter
from repro.core.distributed import flatten_pytree, pad_dim, sharded_aggregate
from repro.fl.async_rounds import AsyncConfig, resolve_async_plan, staleness_of
from repro.data.device import (
    ChunkSchedule,
    DeviceClientStore,
    HostClientStore,
    build_chunk_schedule,
    place_schedule,
)
from repro.data.synthetic import FederatedDataset
from repro.fl.client import (
    BatchedCohortTrainer,
    ShardedCohortTrainer,
    client_batch_rng,
    stack_freeze_flags,
    stack_variant_trees,
    steps_to_run,
)
from repro.fl.metrics import ResourceLedger
from repro.fl.strategy import Strategy
from repro.models.cnn import param_count

PyTree = Any

# Roofline instrumentation hook: when a list is installed here (see
# ``benchmarks/engine.py``), ``_ChunkRunner.run_chunk`` appends the compiled
# chunk program's post-partitioning HLO text on every cache miss.  Lowering
# for capture costs one extra XLA compile, so the hook must stay ``None``
# during any run whose ``compiles_chunk == 1`` sentinel is asserted — capture
# runs are separate, unasserted jobs.
_hlo_capture: Optional[List[str]] = None


def _tree_where(pred, on_true, on_false):
    """Leafwise select with a scalar predicate (freezes the carry post-stop)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(pred, a, b), on_true, on_false
    )


def _bucket_candidates(n: int, cap: int) -> int:
    """Round a chunk's candidate count up to a power of two (capped at M).

    The union of a chunk's cohorts varies chunk to chunk; bucketing the
    candidate axis keeps the jitted chunk program's shapes stable per bucket
    (same discipline as the schedule step axis) instead of retracing every
    chunk.  Pad slots are unreachable — host slots only point at real
    candidates — so padding with a duplicated id is exact.
    """
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap)


def _live_device_bytes() -> int:
    """Bytes in use on the local devices (the driver's memory probe).

    Reads the allocator's ``bytes_in_use`` where the backend reports
    ``memory_stats()`` (TPU/GPU); the CPU backend reports none, so there it
    sums every live array.  Coarse by design: counts everything in the
    process, which is exactly what the flat-in-M acceptance check needs — if
    the paged path leaked O(M) device state, it would show here.
    """
    stats = [d.memory_stats() for d in jax.local_devices()]
    if all(s is not None for s in stats):
        return sum(int(s["bytes_in_use"]) for s in stats)
    return sum(int(a.nbytes) for a in jax.live_arrays())


class _ChunkRunner:
    """Builds and caches the jitted chunk program for one FL job.

    ``mesh=None`` is the single-device path; with a mesh the chunk body runs
    the shard_mapped cohort program and the D-sharded round pipeline.  Either
    way the chunk carry (flat w, strategy carry, stop flag, accuracy) is
    donated: the output buffers alias the inputs, so the O(D)/O(M·D) state
    updates in place chunk over chunk.
    """

    def __init__(self, model, store: Optional[DeviceClientStore], unflatten,
                 program, transform, *, learning_rate: float, batch_size: int,
                 clients_per_round: int, eval_every: int, max_rounds: int,
                 eval_x, eval_y, feat_shape: Tuple[int, ...], mesh=None,
                 paged: bool = False, async_plan=None):
        self.model = model
        # the stores keep each sample's features flat (F values); batches
        # are restored to the dataset's feature shape as they are loaded
        self.feat_shape = tuple(feat_shape)
        # staleness-aware rounds: None ⇒ synchronous chunks (the arrival
        # buffer carry slot is an empty pytree and the body is untouched)
        self.async_plan = async_plan
        # the chunk program takes its samples as ordinary (never donated)
        # inputs: paged mode (store=None) each chunk's (P_cand, N_max, …)
        # page, resident mode the full device store on every call.  Closed
        # over, the store and eval set would be baked into the program as
        # constants (GBs of HLO at the paper's CIFAR width).
        self.paged = paged
        self.unflatten = unflatten
        self.program = program
        self.transform = transform
        self.p = clients_per_round
        self.eval_every = eval_every
        self.max_rounds = max_rounds
        self.mesh = mesh
        if mesh is None:
            self._trainer = BatchedCohortTrainer(model, learning_rate, batch_size)
            self.p_pad = clients_per_round
            self.n_data = 1
        else:
            self._trainer = ShardedCohortTrainer(model, learning_rate, batch_size, mesh)
            self.axes = self._trainer.axes
            self.n_data = mesh.shape[self._trainer.data_axis]
            self.p_pad = pad_dim(clients_per_round, self.n_data)
        self._cache: Dict[Tuple[bool, bool], Any] = {}
        # placed here (setup, outside the dispatch loop) so the recompile
        # sentinel's per-dispatch delta sees only the chunk program itself,
        # and committed (replicated on a mesh) like every other chunk input:
        # an uncommitted single-device input is resharded at every dispatch
        if mesh is None:
            put = jax.device_put
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            put = lambda a: jax.device_put(a, NamedSharding(mesh, P()))
        self.eval_x, self.eval_y = put(eval_x), put(eval_y)
        self._resident = None if paged else (
            store.x, store.y, put(store.sizes.astype(jnp.float32))
        )
        # a model's frozen weights (LoRA's base) enter every chunk as an
        # argument that is never donated: held once on the device, and never
        # baked into the program as constants.  None (no frozen weights)
        # adds no argument.
        self.frozen = getattr(model, "frozen", None)
        if self.frozen is not None:
            self.frozen = jax.tree_util.tree_map(put, self.frozen)

    def _build(self, use_prox: bool, has_mask: bool, carry_shardings=None):
        program, unflatten = self.program, self.unflatten
        p, transform, mesh = self.p, self.transform, self.mesh
        paged, async_plan = self.paged, self.async_plan
        eval_every, max_rounds = self.eval_every, self.max_rounds
        model, trainer = self.model, self._trainer
        feat_shape = self.feat_shape
        # where the store is not split over a ``data`` axis, the trainer
        # gathers one step's batch at a time from it, so a round never holds
        # its whole (P, S, B, F) batch tensor (1.9 GB at the paper's CIFAR
        # width).  A store sharded over ``data`` is instead gathered once per
        # round through the partitioner: the shard_mapped trainer would see
        # it whole and all-gather it onto every device.
        step_gather = self.n_data == 1
        eval_params = self.unflatten
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            axes, p_pad = self.axes, self.p_pad
            rep_sharding = NamedSharding(mesh, P())
            # model-axis composition: the eval-time params of a model-sharded
            # model are pinned to the policy layouts too, so the chunk never
            # materializes a replicated copy of the full model
            param_shardings = trainer.param_shardings
            if param_shardings is not None:
                def eval_params(wv):
                    return jax.tree_util.tree_map(
                        jax.lax.with_sharding_constraint,
                        unflatten(wv), param_shardings,
                    )

        def body_with(cand, page_x, page_y, page_sizes, eval_x, eval_y, frozen):
            """The scan body, closed over this chunk's candidate remap.

            ``cand`` is the chunk's (P_cand,) sorted global candidate ids;
            every per-round index in ``xs`` is a candidate-relative SLOT
            (schedules and pages are slot-indexed), and ``ids = cand[slots]``
            recovers global ids for sizes, the update transform and the
            strategy carry.  Paged mode gathers samples from the page by
            slot; resident mode's "page" is the full store, gathered by
            global id.  With ``step_gather`` the trainer scans sample
            positions and client rows and gathers each step's batch
            (``load``); otherwise it scans the round's gathered batches.
            """

            def unflat(x):
                return x.reshape(*x.shape[:-1], *feat_shape)

            bound = model if frozen is None else model.bind(frozen)

            if step_gather:
                def load(pos, rows):
                    return unflat(page_x[rows, pos]), page_y[rows, pos]
            else:
                def load(x, y):
                    return unflat(x), y

            # a model with frozen weights trains through a trainer over the
            # model bound to this trace's frozen argument
            cohort = trainer if frozen is None else trainer.rebind(bound)
            if mesh is None:
                train = cohort._make_train(load)
            else:
                train_sharded = cohort._sharded_train_raw(use_prox, has_mask, load)

            def body(carry, x_t):
                w, sc, abuf, stopped, last_acc = carry
                t, phi, host_slots, bi_t, sw_t, sv_t, prox_t, mask_t, freeze_t = x_t
                params_t = unflatten(w)

                # --- Alg. 2 selection (device, candidate-relative slots) ----
                # or host-precomputed slots --------------------------------
                with jax.named_scope("fl/select"):
                    if program.select is not None:
                        sc_new, slots, exploited = program.select(sc, t, phi, cand)
                    else:
                        sc_new, slots, exploited = sc, host_slots, jnp.asarray(False)
                    slots = slots.astype(jnp.int32)
                    ids = cand[slots]
                    sel_sizes = page_sizes[slots if paged else ids]

                # --- the cohort's batch schedule (gathered step by step) ----
                with jax.named_scope("fl/train"):
                    if mesh is None:
                        bi = bi_t[slots]
                        rows = (slots if paged else ids)[:, None, None]
                        x, y = bi, jnp.broadcast_to(rows, bi.shape)
                        sw, sv = sw_t[slots], sv_t[slots]
                        mu = prox_t[slots]
                        _, flat, losses, *counts = train(
                            params_t, x, y, sw, sv, mask_t, freeze_t, mu,
                            use_prox=use_prox, has_mask=has_mask,
                        )
                    else:
                        # pad the cohort to the data axis with exact no-op
                        # clients (zero step validity ⇒ identically-zero update
                        # rows) and train shard_mapped over it.  The O(P) index
                        # vectors MUST stay replicated: letting the partitioner
                        # row-shard them over ``data`` miscompiles the
                        # downstream store/schedule gathers (wrong rows,
                        # observed on 2x4 CPU meshes) — a
                        # with_sharding_constraint pins them
                        if p_pad > p:
                            slots_pad = jnp.concatenate(
                                [slots, jnp.zeros((p_pad - p,), jnp.int32)]
                            )
                        else:
                            slots_pad = slots
                        slots_pad = jax.lax.with_sharding_constraint(
                            slots_pad, rep_sharding
                        )
                        bi = bi_t[slots_pad]
                        if paged:
                            rows = slots_pad[:, None, None]
                        else:
                            rows_ids = jax.lax.with_sharding_constraint(
                                cand[slots_pad], rep_sharding
                            )
                            rows = rows_ids[:, None, None]
                        if step_gather:
                            x, y = bi, jnp.broadcast_to(rows, bi.shape)
                        else:
                            x, y = page_x[rows, bi], page_y[rows, bi]
                        sw, sv = sw_t[slots_pad], sv_t[slots_pad]
                        if p_pad > p:
                            valid_row = (jnp.arange(p_pad) < p).astype(sv.dtype)
                            sv = sv * valid_row[:, None]
                        mu = prox_t[slots_pad]
                        _, flat, losses, *counts = train_sharded(
                            params_t, x, y, sw, sv, mask_t, freeze_t, mu
                        )
                if mesh is not None:
                    # the ONE pad-then-all-to-all reshard to the (P, D_pad)
                    # D-sharded round-buffer layout
                    with jax.named_scope("fl/reshard"):
                        flat = trainer.reshard_rows_traced(flat, p)
                        losses, sv = losses[:p], sv[:p]

                # --- device-resident update transform (compression) -------------
                if transform is not None:
                    with jax.named_scope("fl/transform"):
                        flat = transform(t, ids, flat)

                if async_plan is None:
                    abuf_new = abuf
                    tau_hist = None

                    # --- Eq. 4 aggregation from the flat buffer -----------------
                    with jax.named_scope("fl/aggregate"):
                        total = jnp.sum(sel_sizes)
                        weights = jnp.where(total > 0.0, sel_sizes / total, 1.0 / p)
                        if mesh is None:
                            w_new = w + weights @ flat
                        else:
                            w_new = sharded_aggregate(w, flat, weights, mesh, axes)

                    # --- strategy bookkeeping + stop (Alg. 1/3 for FLrce) -------
                    if program.post_round is not None:
                        sc_new, stop = program.post_round(
                            sc_new, t, w, ids, flat, exploited
                        )
                    else:
                        stop = jnp.asarray(False)
                else:
                    # --- staleness-aware round over the arrival ring buffer -----
                    # The departing cohort parks in ring slot t mod B with its
                    # landing round precomputed; the slot's previous occupant
                    # departed B rounds ago and landed at latest S rounds later
                    # (== t-1), so the slot is free by construction.  With
                    # max_staleness=0 (B=1) the cohort is written and lands in
                    # the same round, and every op below reproduces the
                    # synchronous branch bitwise.
                    with jax.named_scope("fl/aggregate"):
                        s_max = async_plan.max_staleness
                        b_depth = async_plan.depth
                        k_slot = jnp.mod(t, b_depth)
                        delays = async_plan.delays(t, ids)
                        t32 = t.astype(jnp.int32)
                        abuf = {
                            "u": abuf["u"].at[k_slot].set(flat),
                            "sizes": abuf["sizes"].at[k_slot].set(sel_sizes),
                            "ids": abuf["ids"].at[k_slot].set(ids),
                            "depart": abuf["depart"].at[k_slot].set(
                                jnp.broadcast_to(t32, (p,))
                            ),
                            "land": abuf["land"].at[k_slot].set(t32 + delays),
                            "valid": abuf["valid"].at[k_slot].set(
                                jnp.ones((p,), bool)
                            ),
                            "anchor": abuf["anchor"].at[k_slot].set(w),
                        }
                        buf_u = abuf["u"].reshape(b_depth * p, -1)
                        buf_sizes = abuf["sizes"].reshape(-1)
                        buf_ids = abuf["ids"].reshape(-1)
                        buf_depart = abuf["depart"].reshape(-1)
                        buf_valid = abuf["valid"].reshape(-1)
                        arrived = jnp.logical_and(
                            buf_valid, abuf["land"].reshape(-1) == t32
                        )
                        tau = jnp.clip(staleness_of(buf_depart, t32), 0, s_max)
                        dw = async_plan.decay_table[tau]

                        # --- staleness-weighted Eq. 4 over this round's arrivals
                        # (weight n_k · decay(τ_k), renormalized; an arrival-free
                        # round leaves w unchanged: all-zero weights)
                        scaled = jnp.where(arrived, buf_sizes * dw, 0.0)
                        total = jnp.sum(scaled)
                        n_arr = jnp.sum(arrived.astype(jnp.float32))
                        weights = jnp.where(
                            total > 0.0,
                            scaled / total,
                            jnp.where(arrived, 1.0 / jnp.maximum(n_arr, 1.0), 0.0),
                        )
                        if mesh is None:
                            w_new = w + weights @ buf_u
                        else:
                            w_new = sharded_aggregate(w, buf_u, weights, mesh, axes)

                        # landed rows leave the buffer; the rest stay pending
                        abuf_new = {
                            **abuf,
                            "valid": jnp.logical_and(
                                buf_valid, jnp.logical_not(arrived)
                            ).reshape(b_depth, p),
                        }
                        tau_hist = (
                            jnp.zeros((b_depth,), jnp.int32)
                            .at[tau]
                            .add(arrived.astype(jnp.int32))
                        )

                    # --- strategy bookkeeping over the arrivals -----------------
                    if program.post_round_async is not None:
                        anchor_rows = jnp.repeat(abuf["anchor"], p, axis=0)
                        sc_new, stop = program.post_round_async(
                            sc_new, t, w, buf_ids, buf_depart, buf_u,
                            anchor_rows, arrived, exploited,
                        )
                    else:
                        stop = jnp.asarray(False)

                # --- per-round stats (device nanmean over clients) --------------
                # and the round's real local steps, and the steps its cohort
                # ran (the trip count of its step loop, on a ``data`` axis
                # over 1 the longest shard's; the rest of the S-step bucket
                # was not run)
                with jax.named_scope("fl/round_stats"):
                    cnt = jnp.sum(sv, axis=1)
                    has = cnt > 0.0
                    mean_k = jnp.where(
                        has, jnp.sum(losses * sv, axis=1) / jnp.maximum(cnt, 1.0), 0.0
                    )
                    n_has = jnp.sum(has.astype(jnp.float32))
                    mean_loss = jnp.where(
                        n_has > 0.0, jnp.sum(mean_k) / jnp.maximum(n_has, 1.0), jnp.nan
                    )
                    useful_steps = jnp.sum(cnt).astype(jnp.int32)
                    steps_run = steps_to_run(sv)
                    if counts:
                        # each MoE layer's largest per-expert count of the
                        # round's routed assignments over the mean count,
                        # averaged over the layers that route
                        per_layer = jnp.sum(counts[0], axis=0)          # (L, E)
                        mean_e = jnp.mean(per_layer, axis=1)
                        routes = mean_e > 0.0
                        ratio = jnp.max(per_layer, axis=1) / jnp.maximum(mean_e, 1e-30)
                        expert_load = jnp.sum(jnp.where(routes, ratio, 0.0)) / jnp.maximum(
                            jnp.sum(routes.astype(jnp.float32)), 1.0)

                # --- evaluation (only when the loop driver would) ---------------
                with jax.named_scope("fl/eval"):
                    evaluated = jnp.logical_or(
                        jnp.logical_or(t % eval_every == 0, stop), t == max_rounds - 1
                    )
                    acc = jax.lax.cond(
                        evaluated,
                        lambda wv: bound.accuracy(
                            eval_params(wv), eval_x, eval_y
                        ).astype(jnp.float32),
                        lambda wv: last_acc,
                        w_new,
                    )

                # rounds after a stop still execute (scan has no early exit) but
                # never touch the carry: the final state is the stop round's.
                # ``stopped`` enters the carry at the CHUNK boundary too, so a
                # speculative chunk dispatched after a stop runs fully masked —
                # its carry out is bitwise its carry in.
                new_carry = (
                    w_new, sc_new, abuf_new, jnp.logical_or(stopped, stop), acc
                )
                carry_out = _tree_where(stopped, carry, new_carry)
                out = {
                    "ids": ids,
                    "exploited": exploited,
                    "stop": stop,
                    "acc": acc,
                    "evaluated": evaluated,
                    "mean_loss": mean_loss,
                    "useful_steps": useful_steps,
                    "steps_run": steps_run,
                    "valid": jnp.logical_not(stopped),
                }
                if counts:
                    out["expert_load"] = expert_load
                if tau_hist is not None:
                    out["tau_hist"] = tau_hist
                return carry_out, out

            return body

        def finish(carry, outs):
            w, sc, abuf, stopped, last_acc = carry
            if carry_shardings is not None:
                # pin the output carry to the INPUT carry's layouts: without
                # this GSPMD is free to emit e.g. FLrce's (M,) round map
                # data-sharded, which changes the next call's jit signature
                # (one silent full recompile per job) and breaks the donated
                # in-place aliasing
                w, sc, abuf, stopped, last_acc = jax.tree_util.tree_map(
                    jax.lax.with_sharding_constraint,
                    (w, sc, abuf, stopped, last_acc), carry_shardings,
                )
            return w, sc, abuf, stopped, last_acc, outs

        def chunk(w, sc, abuf, stopped, last_acc, cand, page_x, page_y,
                  page_sizes, eval_x, eval_y, xs, frozen):
            body = body_with(cand, page_x, page_y, page_sizes, eval_x, eval_y, frozen)
            carry = jax.lax.scan(body, (w, sc, abuf, stopped, last_acc), xs)
            return finish(*carry)

        # donated carry: the chunk's (D[,_pad]) flat model, the strategy
        # carry (FLrce's Ω/H and the V/A maps), the async arrival buffer (an
        # empty pytree on synchronous jobs), the cross-chunk stop flag and
        # the accuracy scalar alias their outputs — no per-chunk copy of the
        # O(M·D) state.  The candidate remap, the page (or resident store)
        # and the eval set are NOT donated: at pipeline depth 2 the two
        # in-flight chunks each hold their own page, and the store, the eval
        # set and the frozen weights serve every chunk.
        return jax.jit(chunk, donate_argnums=(0, 1, 2, 3, 4))

    def run_chunk(self, w, sc, abuf, stopped, last_acc, cand, page, xs,
                  use_prox: bool, has_mask: bool):
        key = (use_prox, has_mask)
        page_x, page_y, page_sizes = page if self.paged else self._resident
        args = (w, sc, abuf, stopped, last_acc, cand, page_x, page_y,
                page_sizes, self.eval_x, self.eval_y, xs, self.frozen)
        if key not in self._cache:
            shardings = None
            if self.mesh is not None:
                shardings = jax.tree_util.tree_map(
                    lambda l: l.sharding, (w, sc, abuf, stopped, last_acc)
                )
            self._cache[key] = self._build(use_prox, has_mask, shardings)
            if _hlo_capture is not None:
                # roofline capture: the post-partitioning (per-device) HLO of
                # the compiled chunk.  Donation is ignored for the side
                # lowering, so the live carry stays valid for the real call
                # below; the extra compile is why capture runs are never
                # compile-sentinel-asserted.
                _hlo_capture.append(
                    self._cache[key]
                    .lower(*args)
                    .compile()
                    .as_text()
                )
        return self._cache[key](*args)


@dataclasses.dataclass
class _ChunkPlan:
    """One chunk's host-built inputs, ready for (or already in) flight."""

    t0: int
    r: int
    cand: np.ndarray              # (n_cand,) sorted global candidate ids (real)
    cand_dev: Any                 # (P_cand,) int32 device candidate remap
    page: Optional[Tuple]         # paged store: (page_x, page_y, page_sizes_f)
    cfg_grid: List[List[Any]]     # (R, n_cand) LocalConfigs — reused at flush
    xs: Tuple                     # the scan's stacked per-round inputs
    use_prox: bool
    has_mask: bool
    steps: int                    # S, the chunk's step bucket
    sched_bytes: int              # host bytes of this chunk's schedules
    page_bytes: int               # H2D bytes of this chunk's page (paged only)


def run_scan_driver(
    model,
    dataset: FederatedDataset,
    strategy: Strategy,
    *,
    max_rounds: int,
    learning_rate: float,
    batch_size: int,
    device: str,
    eval_every: int,
    seed: int,
    init_params: Optional[PyTree],
    verbose: bool,
    chunk_rounds: int,
    mesh=None,
    pipeline: bool = True,
    paged: bool = False,
    async_rounds: Optional[AsyncConfig] = None,
):
    """Algorithm 4's outer loop as jitted round chunks.  Called by
    ``run_federated(driver="scan")`` — with ``mesh`` for
    ``engine="sharded"`` — and returns the same :class:`FLResult`.

    ``pipeline=True`` (default) runs the chunk loop as a two-deep software
    pipeline — chunk k+1 is built, transferred and dispatched while the host
    consumes chunk k — ``pipeline=False`` is the strictly serial
    build → run → flush loop (same loop at depth 1, bitwise-equal results).

    ``paged=True`` (``run_federated(client_store="paged")``) swaps the
    device-resident client store for a :class:`HostClientStore`: the
    (M, N_max, …) universe stays in host memory and each chunk uploads only
    its candidate rows as a fresh slot-indexed page, double-buffered on the
    same pipeline.  Device memory becomes O(P_cand) flat in M; with the
    default full-universe candidates the results stay bitwise the resident
    driver's.

    ``async_rounds=AsyncConfig(...)`` (``run_federated(async_rounds=...)``)
    runs staleness-aware rounds: departing cohorts park in a ring buffer in
    the donated carry and land ``τ ∈ [0, max_staleness]`` rounds later under
    the staleness-weighted Eq. 4 (see the module docstring).  Requires the
    resident store and, for strategies with per-round bookkeeping, a
    ``post_round_async`` hook; ``max_staleness=0`` reproduces the
    synchronous driver bitwise.
    """
    from repro.fl.rounds import RoundRecord, finalize_result

    if chunk_rounds < 1:
        raise ValueError(f"chunk_rounds must be >= 1, got {chunk_rounds}")
    if paged and not strategy.supports_paged_store:
        raise ValueError(
            f"{strategy.name} does not support client_store='paged' "
            "(supports_paged_store is False)"
        )
    if mesh is not None:
        # O(D) strategy state (FLrce's V/A maps) moves onto the mesh BEFORE
        # the carry is exported, so scan_program() hands out sharded arrays
        strategy.bind_mesh(mesh, tuple(mesh.axis_names))
    program = strategy.scan_program()
    if program.post_round is not None and program.select is None:
        raise ValueError(
            "a ScanProgram with post_round needs device-side select: a "
            "host-selected chunk cannot react to a device stop mid-chunk"
        )
    if program.select is not None and program.explore_phis is None:
        raise ValueError("a ScanProgram with device select must provide explore_phis")
    if async_rounds is not None:
        if paged:
            raise ValueError(
                "async_rounds requires client_store='resident': the paged "
                "store's per-chunk candidate pages cannot cover cohorts that "
                "land in a later chunk"
            )
        if program.post_round is not None and program.post_round_async is None:
            raise ValueError(
                f"{strategy.name}'s ScanProgram has per-round bookkeeping "
                "(post_round) but no post_round_async: async rounds would "
                "silently feed stale arrivals to the synchronous hook "
                "(FLrce withholds the async hook under sketched V/A maps)"
            )

    stats: Dict[str, Any] = {
        "driver": "scan",
        "pipeline": bool(pipeline),
        "store": "paged" if paged else "resident",
        "chunks": 0,
        "speculative_chunks": 0,
        "cancelled_chunks": 0,
        "setup_store_s": 0.0,
        "host_build_s": 0.0,
        "dispatch_s": 0.0,
        "device_wait_s": 0.0,
        "host_flush_s": 0.0,
        "compile_s": 0.0,
        "total_s": 0.0,
        "steps_useful": 0,
        "steps_executed": 0,
        "steps_bucket": 0,
        "schedule_bytes_host": 0,
        "page_bytes_h2d": 0,
        "peak_live_bytes": 0,
    }

    @contextlib.contextmanager
    def span(name: str, key: Optional[str] = None, chunk: Optional[int] = None):
        """One host span: a profiler ``TraceAnnotation`` (which records
        nothing while no trace runs), its seconds added to ``stats[key]``.
        ``chunk`` (the chunk's first round, shared by every span of one
        chunk) rides on the trace event as a stat."""
        t = time.perf_counter()
        meta = {} if chunk is None else {"chunk": chunk}
        with jax.profiler.TraceAnnotation(name, **meta):
            yield
        if key is not None:
            stats[key] += time.perf_counter() - t

    params = init_params if init_params is not None else model.init(jax.random.PRNGKey(seed))
    n_params = param_count(params)
    w, unflatten = flatten_pytree(params)
    with span("fl.setup.store", "setup_store_s"):
        if paged:
            # fleet-scale layout: the (M, N_max, …) universe stays HOST-side;
            # chunks page their candidate rows on demand (O(P_cand) device memory)
            store = HostClientStore.from_dataset(dataset)
        else:
            # with a mesh the store is placed data-axis-sharded in ONE transfer
            # (enqueued here; chunk 0's dispatch waits for it)
            store = DeviceClientStore.from_dataset(dataset, mesh=mesh)
    m = store.num_clients
    ledger = ResourceLedger(device=device)
    # the strategy's device-resident update post-processing (Fedcom top-k,
    # QuantizedFL int8) traces straight into the compiled chunk
    transform = strategy.update_transform(params)
    if mesh is not None:
        if transform is not None:
            raise ValueError(
                f"{strategy.name} declares an update_transform, which operates "
                "on the replicated flat matrix; the mesh-sharded chunks do not "
                "support it (supports_sharded_scan must be False)"
            )
        from jax.sharding import NamedSharding, PartitionSpec

        axes = tuple(mesh.axis_names)
        from repro.core.distributed import mesh_axes_size

        d_pad = pad_dim(n_params, mesh_axes_size(mesh, axes))
        w = jax.device_put(
            jnp.pad(w, (0, d_pad - n_params)),
            NamedSharding(mesh, PartitionSpec(axes)),
        )
    async_plan = None
    if async_rounds is not None:
        # the plan's lookup tables (decay, trace) are replicated chunk
        # constants — same placement discipline as the other chunk inputs
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            _rep = NamedSharding(mesh, PartitionSpec())
        else:
            _rep = next(iter(w.devices()))
        async_plan = resolve_async_plan(
            async_rounds, num_clients=m, seed=seed,
            put=lambda a: jax.device_put(a, _rep),
        )
    runner = _ChunkRunner(
        model, None if paged else store, unflatten, program, transform,
        learning_rate=learning_rate, batch_size=batch_size,
        clients_per_round=strategy.p, eval_every=eval_every,
        max_rounds=max_rounds,
        eval_x=dataset.eval_x, eval_y=dataset.eval_y,
        feat_shape=dataset.x.shape[1:],
        mesh=mesh, paged=paged, async_plan=async_plan,
    )

    sc = program.carry
    if mesh is None:
        # a strategy whose carry was bound to a multi-device mesh (a prior
        # engine="sharded" run on the same object) cannot enter the
        # single-device chunk: its O(D) state is padded/sharded for that
        # mesh and the trace would fail with an opaque shape error
        for leaf in jax.tree_util.tree_leaves(sc):
            sh = getattr(leaf, "sharding", None)
            if getattr(leaf, "committed", False) and len(leaf.devices()) > 1:
                raise ValueError(
                    f"{strategy.name}'s scan carry is bound to a multi-device "
                    f"mesh ({sh}); run with engine='sharded' (pass the mesh) "
                    "or use a freshly constructed strategy"
                )
    # Commit the initial carry with its steady-state placement.  From chunk
    # 2 on, the carry arrives as the previous chunk's committed outputs; an
    # uncommitted first carry would give chunk 1 a different jit signature
    # and force ONE full recompile of the chunk program on the second call.
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(mesh, PartitionSpec())
    else:
        rep = next(iter(w.devices()))
    commit = lambda l: l if getattr(l, "committed", False) else jax.device_put(l, rep)
    w = commit(w)
    sc = jax.tree_util.tree_map(commit, sc)
    es_flag = commit(jnp.asarray(False))   # the cross-chunk stop flag
    last_acc = commit(jnp.float32(0.0))

    # the async arrival ring buffer rides in the donated carry: B = S+1
    # slots of one (P, D) pending cohort each, plus its departure-round
    # anchor models.  Synchronous jobs carry an empty pytree instead — the
    # chunk program is byte-identical to the pre-async driver's.
    abuf: Any = {}
    if async_plan is not None:
        b_depth, p_sel, d_flat = async_plan.depth, strategy.p, int(w.shape[0])
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            # O(D) buffers live D-sharded like the round buffers they hold;
            # the O(B·P) metadata stays replicated
            put_u = lambda a: jax.device_put(
                a, NamedSharding(mesh, PartitionSpec(None, None, axes))
            )
            put_anchor = lambda a: jax.device_put(
                a, NamedSharding(mesh, PartitionSpec(None, axes))
            )
        else:
            put_u = put_anchor = commit
        abuf = {
            "u": put_u(jnp.zeros((b_depth, p_sel, d_flat), jnp.float32)),
            "sizes": commit(jnp.zeros((b_depth, p_sel), jnp.float32)),
            "ids": commit(jnp.zeros((b_depth, p_sel), jnp.int32)),
            "depart": commit(jnp.zeros((b_depth, p_sel), jnp.int32)),
            "land": commit(jnp.full((b_depth, p_sel), -1, jnp.int32)),
            "valid": commit(jnp.zeros((b_depth, p_sel), bool)),
            "anchor": put_anchor(jnp.zeros((b_depth, d_flat), jnp.float32)),
        }

    # ------------------------------------------------------------------
    # host-side chunk phases: build (pre-device) and flush (post-device)
    # ------------------------------------------------------------------
    def build_chunk(t0: int) -> _ChunkPlan:
        """Everything a chunk needs before dispatch: candidates, configs,
        schedules, variant inputs, H2D placement (page included).  Pure host
        + async transfers — safe to run one chunk ahead of the flush (all of
        it is a pure function of ``(strategy, seed, t0)``, never of round
        results)."""
        r = min(chunk_rounds, max_rounds - t0)
        ts = list(range(t0, t0 + r))

        with span("fl.build.candidates", chunk=t0):
            # ---- candidate set (the chunk program's client index space) ---
            if program.select is None:
                # host-precomputed selection: the candidate set is exactly the
                # union of the chunk's cohorts — always exact.  Bucketed to a
                # power of two (pad = duplicated last id) so the jitted chunk
                # keeps a stable candidate-axis shape; pad slots are unreachable
                # because host slots only point at real candidates.
                host_ids = np.stack(
                    [np.asarray(strategy.select(t)) for t in ts]
                ).astype(np.int64)
                cand = np.unique(host_ids)
                n_bucket = _bucket_candidates(len(cand), m)
                cand_pad = np.concatenate(
                    [cand, np.full(n_bucket - len(cand), cand[-1], np.int64)]
                )
                host_slots = np.searchsorted(cand, host_ids).astype(np.int32)
                phis = np.zeros(r, np.float32)
            else:
                # device-side selection: the strategy proposes a candidate
                # superset (None ⇒ full universe — the exact-equivalence mode,
                # where slots ≡ ids bitwise).  NEVER padded: ``top_k`` over the
                # candidate heuristic could select a duplicated pad row.
                host_ids = None
                proposal = strategy.propose_candidates(np.asarray(ts))
                if proposal is None:
                    cand = np.arange(m, dtype=np.int64)
                else:
                    cand = np.asarray(proposal, np.int64)
                    if (
                        cand.ndim != 1
                        or len(cand) < strategy.p
                        or len(np.unique(cand)) != len(cand)
                        or np.any(np.diff(cand) < 0)
                        or (len(cand) and (cand[0] < 0 or cand[-1] >= m))
                    ):
                        raise ValueError(
                            f"{strategy.name}.propose_candidates must return sorted "
                            f"unique ids in [0, {m}) with P_cand >= P={strategy.p}; "
                            f"got shape {cand.shape}"
                        )
                cand_pad = cand
                host_slots = np.zeros((r, strategy.p), np.int32)
                phis = program.explore_phis(np.asarray(ts))

        with span("fl.build.configs", chunk=t0):
            # per-(round, candidate) local configs: epochs/prox enter the
            # compiled chunk; the ledger fractions are reused host-side at flush.
            # The None template means metadata-only (no mask materialization per
            # candidate) — client_config purity makes the forms interchangeable.
            # O(R · P_cand) host work, not O(R · M): only candidate columns exist.
            cfg_grid = [
                [strategy.client_config(t, int(cid), None) for cid in cand_pad]
                for t in ts
            ]
            for row in cfg_grid:
                for cfg in row:
                    if cfg.mask is not None:
                        raise ValueError(
                            f"{strategy.name} materialized a mask from "
                            "client_config(t, cid, None); with a None template "
                            "the config must be metadata-only"
                        )
            epochs = np.asarray([[cfg.epochs for cfg in row] for row in cfg_grid], np.int32)
            prox = np.asarray([[cfg.prox_mu for cfg in row] for row in cfg_grid], np.float32)
            use_prox = bool(np.any(prox > 0.0))
            # both the mesh chunks and device-side selection forbid per-cohort
            # variants — one O(R·P_cand) sweep establishes the invariant for
            # either (cheap for a compliant strategy: its configs are
            # metadata-only, and misuse costs an error, not silence)
            if mesh is not None or program.select is not None:
                if any(
                    cfg.freeze_frac for row in cfg_grid for cfg in row
                ) or any(
                    strategy.client_config(t, int(cid), params).mask is not None
                    for t in ts for cid in cand
                ):
                    raise ValueError(
                        f"{strategy.name} uses per-client masks or freeze flags; "
                        + ("the mesh-sharded chunks need metadata-only configs "
                           "(supports_sharded_scan must be False)"
                           if mesh is not None else
                           "with device-side selection they cannot be precomputed "
                           "for the selected cohort (host-precomputable selection "
                           "is required)")
                    )

        # batch schedules from the SAME fold-in streams the loop engines use;
        # per-candidate columns (client_ids) keep host bytes O(P_cand), and
        # the memo keys by GLOBAL id so dense and compact builds share hits
        with span("fl.build.schedule", chunk=t0):
            sched = build_chunk_schedule(
                store.sizes_host[cand_pad], epochs, batch_size, t0,
                lambda t, cid: client_batch_rng(seed, t, cid),
                cache_key=seed, client_ids=cand_pad,
            )
        with span("fl.build.variants", chunk=t0):
            if program.select is None:
                # the selected cohorts are known, so per-round masks (Dropout)
                # and per-leaf freeze flags (TimelyFL) are materialized host-side
                # — pure re-invocation with the shape template — and ride into
                # the scan as stacked (R, P, ...) inputs.  The mesh chunks take
                # neither (validated above): their variant inputs are all-pass.
                if mesh is not None:
                    has_mask = False
                    mask_xs = {}
                    freeze_rounds = [
                        stack_freeze_flags(params, [0.0] * runner.p_pad) for _ in ts
                    ]
                else:
                    sel_cfgs = [
                        [strategy.client_config(t, int(cid), params) for cid in host_ids[i]]
                        for i, t in enumerate(ts)
                    ]
                    mask_rounds = [
                        stack_variant_trees([c.mask for c in row], params) for row in sel_cfgs
                    ]
                    has_mask = any(flag for _, flag in mask_rounds)
                    if has_mask:
                        ones = jax.tree_util.tree_map(
                            lambda l: jnp.ones((strategy.p,) + l.shape, l.dtype), params
                        )
                        mask_xs = jax.tree_util.tree_map(
                            lambda *ls: jnp.stack(ls),
                            *[mt if flag else ones for mt, flag in mask_rounds],
                        )
                    else:
                        mask_xs = {}
                    freeze_rounds = [
                        stack_freeze_flags(params, [c.freeze_frac for c in row])
                        for row in sel_cfgs
                    ]
            else:
                # device-side selection: the cohort is unknown at chunk build, so
                # per-round host-built variants cannot be gathered for it (no
                # masks/freeze — established by the shared sweep above)
                has_mask = False
                mask_xs = {}
                freeze_rounds = [
                    stack_freeze_flags(params, [0.0] * runner.p_pad) for _ in ts
                ]
            freeze_xs = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *freeze_rounds)

        with span("fl.build.place", chunk=t0):
            # fresh device buffers every chunk (double-buffered by construction):
            # the async H2D copies for chunk k+1 — schedules, the candidate
            # remap and (paged) the page — overlap chunk k's execution and never
            # alias tensors a running chunk still reads
            bi_xs, sw_xs, sv_xs = place_schedule(sched, mesh)
            # every other chunk input is pinned to an explicit replicated
            # placement: an unpinned single-device array would be resharded by
            # every mesh dispatch through jitted slice helpers — a per-chunk
            # recompile the engine's compile sentinel rejects
            if mesh is None:
                put = jax.device_put
            else:
                from jax.sharding import NamedSharding, PartitionSpec

                rep = NamedSharding(mesh, PartitionSpec())
                put = lambda a: jax.device_put(a, rep)
            cand_dev = put(cand_pad.astype(np.int32))
            page = None
            page_bytes = 0
            if paged:
                pstore = store.page(cand_pad, mesh=mesh)
                page = (pstore.x, pstore.y, pstore.sizes.astype(jnp.float32))
                page_bytes = int(pstore.x.nbytes) + int(pstore.y.nbytes)
            xs = (
                put(np.arange(t0, t0 + r, dtype=np.int32)),
                put(np.asarray(phis)),
                put(np.asarray(host_slots)),
                bi_xs,
                sw_xs,
                sv_xs,
                put(np.asarray(prox)),
                mask_xs,
                jax.tree_util.tree_map(put, freeze_xs),
            )
        return _ChunkPlan(t0=t0, r=r, cand=cand, cand_dev=cand_dev, page=page,
                          cfg_grid=cfg_grid, xs=xs,
                          use_prox=use_prox, has_mask=has_mask,
                          steps=sched.num_steps,
                          sched_bytes=int(sched.nbytes), page_bytes=page_bytes)

    records: List[RoundRecord] = []
    load_rounds: List[float] = []            # expert_load of each flushed round

    def flush_chunk(plan: _ChunkPlan, outs) -> Tuple[int, bool]:
        """Consume one chunk's host-fetched outputs: ledger + RoundRecords +
        the stop check.  Returns ``(rounds flushed, chunk stopped)``."""
        flushed = 0
        chunk_stopped = False
        for i in range(plan.r):
            if not outs["valid"][i]:
                break
            t = plan.t0 + i
            ids = [int(c) for c in outs["ids"][i]]
            for cid in ids:
                # cfg_grid columns are candidate slots; recover the slot from
                # the global id (cand is sorted unique, so searchsorted is an
                # exact inverse for any selected id)
                cfg = plan.cfg_grid[i][int(np.searchsorted(plan.cand, cid))]
                flops = (
                    model.flops_per_sample() * int(store.sizes_host[cid])
                    * cfg.epochs * cfg.compute_fraction
                )
                ledger.charge_training(flops)
                ledger.charge_download(n_params, cfg.download_fraction)
                ledger.charge_upload(n_params, cfg.upload_fraction)
            if "tau_hist" in outs:
                # async rounds: uploads were charged above at DEPARTURE (the
                # cohort trained and sent this round); what lands now is only
                # recorded, with its staleness — total charges stay identical
                # to the synchronous run's
                hist = np.asarray(outs["tau_hist"][i])
                ledger.record_arrivals(hist)
                stats["async_arrivals"] += int(hist.sum())
            ledger.end_round()
            rec = RoundRecord(
                t=t,
                accuracy=float(outs["acc"][i]),
                mean_client_loss=float(outs["mean_loss"][i]),
                energy_kj=ledger.energy_j / 1e3,
                bytes_gb=ledger.total_bytes / 1e9,
                selected=ids,
                exploited=bool(outs["exploited"][i]),
                stopped=bool(outs["stop"][i]),
                wall_s=0.0,                    # chunk wall amortized below
                evaluated=bool(outs["evaluated"][i]),
            )
            records.append(rec)
            stats["steps_useful"] += int(outs["useful_steps"][i])
            if "expert_load" in outs:
                load_rounds.append(float(outs["expert_load"][i]))
                stats["expert_load_max_over_mean"] = float(np.mean(load_rounds))
            stats["steps_executed"] += runner.p_pad * int(outs["steps_run"][i])
            stats["steps_bucket"] += runner.p_pad * plan.steps
            flushed += 1
            if verbose:
                print(
                    f"[{strategy.name}] round {t:3d} acc={rec.accuracy:.4f} "
                    f"loss={rec.mean_client_loss:.4f} stop={rec.stopped}"
                )
            if rec.stopped:
                chunk_stopped = True
                break
        return flushed, chunk_stopped

    # ------------------------------------------------------------------
    # the chunk loop: a software pipeline of depth 1 (serial) or 2
    # ------------------------------------------------------------------
    # Depth 2 overlaps BOTH host phases with device compute: chunk k+1 is
    # built + H2D-transferred + dispatched while chunk k executes, and the
    # host then blocks only on chunk k's outputs (the pipeline's first sync
    # point) while chunk k+1 runs.  The second sync point is implicit: chunk
    # k+1's dispatch consumes chunk k's donated carry outputs, so XLA
    # serializes the two programs on-device without any host wait.  Because
    # chunk k's stop decision lands after chunk k+1 is dispatched, the
    # dispatch is speculative — the carried stop flag makes a post-stop chunk
    # a bitwise no-op (all rounds valid=False), and its outputs are dropped
    # here unread, so truncation recovers the serial driver's exact records,
    # ledger and write-back state.
    depth = 2 if pipeline else 1
    if async_plan is not None:
        stats["async_max_staleness"] = async_plan.max_staleness
        stats["async_arrivals"] = 0
    pending: "deque[Tuple[_ChunkPlan, Any]]" = deque()
    stopped = False
    any_flushed = False
    last_exploit = False
    t_final = 0
    t_dispatch = 0
    # Recompile sentinel: `compiles_chunk` counts XLA compilations observed
    # across chunk dispatches — with pinned carry layouts and pow2-bucketed
    # candidate shapes this is exactly 1 per (strategy, mesh, knobs) job, and
    # any drift is the silent-recompile regression the sentinel exists to
    # catch.  `compiles_total` additionally includes programs compiled
    # outside dispatch: eager device ops of the build and the flush (the
    # freeze-flag stacks among them), compiled the first time they run.
    # `compile_s` is the seconds of all of them: set-up, inside ``dispatch_s``
    # for the chunk program and inside the other phases' timers for the rest.
    stats["compiles_chunk"] = 0
    with CompileCounter() as compile_counter, span("fl.job", "total_s"):
        flush_mark = time.perf_counter()
        while pending or (t_dispatch < max_rounds and not stopped):
            # fill the pipeline: build chunk inputs (host), place them (async
            # H2D) and dispatch (async) — never blocking on in-flight chunks
            while len(pending) < depth and t_dispatch < max_rounds and not stopped:
                t0 = t_dispatch
                with span("fl.build", "host_build_s", chunk=t0):
                    plan = build_chunk(t0)
                with span("fl.dispatch", "dispatch_s", chunk=t0), \
                        compile_counter.delta() as compiled:
                    w, sc, abuf, es_flag, last_acc, outs = runner.run_chunk(
                        w, sc, abuf, es_flag, last_acc, plan.cand_dev, plan.page,
                        plan.xs, plan.use_prox, plan.has_mask,
                    )
                stats["compiles_chunk"] += compiled.compiles
                stats["schedule_bytes_host"] += plan.sched_bytes
                stats["page_bytes_h2d"] += plan.page_bytes
                if pending:
                    stats["speculative_chunks"] += 1
                pending.append((plan, outs))
                t_dispatch += plan.r

            plan, outs = pending.popleft()
            with span("fl.wait", "device_wait_s", chunk=plan.t0):
                outs = jax.device_get(outs)        # the chunk's ONE host sync
            # sampled when the pipeline is fullest (this chunk's buffers are
            # still live, the next chunk's page/schedules already transferred) —
            # the flat-in-M acceptance probe for the paged store
            stats["peak_live_bytes"] = max(stats["peak_live_bytes"], _live_device_bytes())

            with span("fl.flush", "host_flush_s", chunk=plan.t0):
                flushed, chunk_stopped = flush_chunk(plan, outs)
                if flushed:
                    any_flushed = True
                    last_exploit = bool(outs["exploited"][flushed - 1])
                    t_final = plan.t0 + flushed
                # chunk wall: everything since the previous flush completed
                # (schedule build + compiled chunk + flush bookkeeping — under
                # pipelining the phases overlap, so consecutive flush-to-flush
                # deltas are the partition of total wall time), amortized over
                # the flushed rounds
                now = time.perf_counter()
                wall, flush_mark = now - flush_mark, now
                for rec in records[-flushed:] if flushed else []:
                    rec.wall_s = wall / flushed
                if chunk_stopped:
                    stopped = True
                    # speculative chunks past the stop ran fully masked: their
                    # carry outputs are bitwise the stop round's state, their
                    # rounds all invalid — drop the outputs unread
                    stats["cancelled_chunks"] += len(pending)
                    pending.clear()
                stats["chunks"] += 1
            # the carry write-back waits until the carry is settled: with no
            # chunk in flight, ``sc`` is exactly the flushed state (serial mode:
            # every chunk; pipelined: the final chunk or the post-stop freeze)
            if not pending and any_flushed and program.finalize is not None:
                program.finalize(sc, t_final, last_exploit)
    stats["compiles_total"] = compile_counter.compiles
    stats["compile_s"] = compile_counter.seconds
    if async_plan is not None:
        # updates still parked in the buffer when the job ended (stop or
        # round budget): departed + charged, never landed
        stats["async_pending_at_exit"] = int(
            np.sum(np.asarray(jax.device_get(abuf["valid"])))
        )
    return finalize_result(
        strategy=strategy,
        records=records,
        stopped=stopped,
        ledger=ledger,
        final_params=unflatten(w),
        driver_stats=stats,
    )
