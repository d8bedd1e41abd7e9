"""Client-side local training (paper Eq. 3, Alg. 4 'Locally' block).

Two execution paths produce the same math (see DESIGN.md §Engine):

* :class:`ClientTrainer` — the sequential reference.  One jitted SGD step per
  (model, variant), called client-by-client and step-by-step from Python.
* :class:`BatchedCohortTrainer` — the production path.  The whole selected
  cohort's local training runs as ONE jitted program: a loop over the
  (padded) step axis of the SGD step ``vmap``-ed over the client axis, run
  only to the cohort's last real step.  A single device round-trip returns
  the stacked update pytree, the flat (P, D) update matrix, and the
  per-client loss traces.

Variants cover the baselines' local tweaks in both paths:

* ``prox_mu``       — Fedprox proximal term  µ/2‖w − w_global‖²
* ``mask``          — Dropout sub-model training (masked params/grads)
* ``freeze_frac``   — TimelyFL layer freezing (earlier fraction of leaves frozen)

The returned *update* is ``w_local − w_global`` accumulated over all local
epochs, matching the paper's u_k (the aggregate of E epochs of SGD).
"""
from __future__ import annotations

import copy
import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.loader import bucket_steps as _bucket_steps, epoch_batches

PyTree = Any

# a round's batch randomness: one shared stream (legacy, consumed
# client-major) or one independent fold-in stream per client
CohortRngs = Union[np.random.Generator, Sequence[np.random.Generator]]


def client_batch_rng(seed: int, t: int, cid: int) -> np.random.Generator:
    """Placement-independent batch RNG: fold (seed, round, client) into one
    independent stream.

    A client's shuffle sequence depends only on this triple — never on its
    position in the cohort, the cohort's composition, or which mesh shard it
    lands on — so the sequential, batched and sharded engines all draw
    identical batches per client.
    """
    entropy = [int(seed) & 0xFFFFFFFFFFFFFFFF, int(t), int(cid)]
    return np.random.default_rng(np.random.SeedSequence(entropy))


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda x, y: x - y, a, b)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda x, y: x + y, a, b)


def tree_mul(a: PyTree, s) -> PyTree:
    return jax.tree_util.tree_map(lambda x: x * s, a)


def _freeze_mask(params: PyTree, freeze_frac: float) -> PyTree:
    """1.0 for trainable leaves, 0.0 for the frozen prefix (layer freezing)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    n = len(leaves)
    n_frozen = int(freeze_frac * n)
    flags = [0.0 if i < n_frozen else 1.0 for i in range(n)]
    return jax.tree_util.tree_unflatten(treedef, [jnp.asarray(f) for f in flags])


class ClientTrainer:
    """Runs E local epochs of SGD for any classifier model."""

    def __init__(self, model, learning_rate: float, batch_size: int):
        self.model = model
        self.lr = learning_rate
        self.batch_size = batch_size
        self._step = jax.jit(self._make_step(), static_argnames=("use_prox",))

    def _make_step(self):
        model, lr = self.model, self.lr

        def step(params, anchor, x, y, mask, freeze, prox_mu, *, use_prox: bool):
            def loss_fn(p):
                if mask is not None:
                    p = jax.tree_util.tree_map(lambda a, m: a * m, p, mask)
                base = model.loss(p, x, y)
                if use_prox:
                    sq = sum(
                        jnp.sum(jnp.square(a - b))
                        for a, b in zip(
                            jax.tree_util.tree_leaves(p),
                            jax.tree_util.tree_leaves(anchor),
                        )
                    )
                    base = base + 0.5 * prox_mu * sq
                return base

            loss, grads = jax.value_and_grad(loss_fn)(params)
            if mask is not None:
                grads = jax.tree_util.tree_map(lambda g, m: g * m, grads, mask)
            if freeze is not None:
                grads = jax.tree_util.tree_map(lambda g, f: g * f, grads, freeze)
            new_params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
            return new_params, loss

        return step

    def local_update(
        self,
        global_params: PyTree,
        x: np.ndarray,
        y: np.ndarray,
        epochs: int,
        rng: np.random.Generator,
        *,
        prox_mu: float = 0.0,
        mask: Optional[PyTree] = None,
        freeze_frac: float = 0.0,
    ) -> Tuple[PyTree, Dict[str, float]]:
        """Returns (update pytree u_k, stats)."""
        params = global_params
        freeze = _freeze_mask(global_params, freeze_frac) if freeze_frac > 0 else None
        losses = []
        n_samples = 0
        for _ in range(max(1, epochs)):
            for bx, by in epoch_batches(x, y, self.batch_size, rng):
                params, loss = self._step(
                    params,
                    global_params,
                    jnp.asarray(bx),
                    jnp.asarray(by),
                    mask,
                    freeze,
                    prox_mu,
                    use_prox=prox_mu > 0.0,
                )
                losses.append(float(loss))
                n_samples += len(bx)
        update = tree_sub(params, global_params)
        if mask is not None:
            update = jax.tree_util.tree_map(lambda u, m: u * m, update, mask)
        stats = {
            "mean_loss": float(np.mean(losses)) if losses else float("nan"),
            "final_loss": losses[-1] if losses else float("nan"),
            "samples_processed": float(n_samples),
            "steps": float(len(losses)),
        }
        return update, stats


# ---------------------------------------------------------------------------
# Batched (vmapped) cohort training
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CohortPlan:
    """Padded, device-ready batch schedule for one round's selected cohort.

    Ragged client datasets are padded along two axes: within a batch (zero
    sample weight) and along the step axis (zero step validity).  Invalid
    steps and padded samples contribute nothing to losses or gradients, so a
    padded schedule reproduces the sequential engine's math exactly.
    """

    x: np.ndarray            # (P, S, B, *feat)
    y: np.ndarray            # (P, S, B) int32
    sample_w: np.ndarray     # (P, S, B) float32: 1 = real sample, 0 = pad
    step_valid: np.ndarray   # (P, S) float32: 1 = real step, 0 = pad
    epochs: List[int]
    num_samples: List[int]

    @property
    def num_clients(self) -> int:
        return self.x.shape[0]

    @property
    def num_steps(self) -> int:
        return self.x.shape[1]


def build_cohort_plan(
    client_data: Sequence[Tuple[np.ndarray, np.ndarray]],
    epochs: Sequence[int],
    batch_size: int,
    rng: CohortRngs,
    *,
    bucket_steps: bool = True,
) -> CohortPlan:
    """Stack every selected client's shuffled epoch batches into one schedule.

    ``rng`` is either one shared host Generator — consumed exactly in the
    order the sequential engine does (client-major, epoch-minor, one
    ``permutation`` per epoch) — or a sequence of per-client Generators (the
    :func:`client_batch_rng` fold-in streams), which makes a client's batches
    independent of cohort order and therefore placement-independent: any
    subset of clients, built in any order or on any shard, draws the same
    schedules.
    """
    if not client_data:
        raise ValueError("empty cohort")
    if isinstance(rng, np.random.Generator):
        rngs: List[np.random.Generator] = [rng] * len(client_data)
    else:
        rngs = list(rng)
        if len(rngs) != len(client_data):
            raise ValueError(
                f"got {len(rngs)} per-client rngs, expected {len(client_data)}"
            )
    feat = client_data[0][0].shape[1:]
    per_client: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
    steps_per_client: List[int] = []
    for (x, y), e, rng_k in zip(client_data, epochs, rngs):
        n = len(x)
        nb = -(-n // batch_size) if n else 0
        s_k = max(1, int(e)) * nb
        bx = np.zeros((s_k, batch_size, *feat), np.float32)
        by = np.zeros((s_k, batch_size), np.int32)
        bw = np.zeros((s_k, batch_size), np.float32)
        s = 0
        for _ in range(max(1, int(e))):
            order = rng_k.permutation(n)
            for start in range(0, n, batch_size):
                ix = order[start : start + batch_size]
                bx[s, : len(ix)] = x[ix]
                by[s, : len(ix)] = y[ix]
                bw[s, : len(ix)] = 1.0
                s += 1
        per_client.append((bx, by, bw))
        steps_per_client.append(s_k)

    s_max = max(max(steps_per_client), 1)
    s_pad = _bucket_steps(s_max) if bucket_steps else s_max
    p = len(client_data)
    px = np.zeros((p, s_pad, batch_size, *feat), np.float32)
    py = np.zeros((p, s_pad, batch_size), np.int32)
    pw = np.zeros((p, s_pad, batch_size), np.float32)
    pv = np.zeros((p, s_pad), np.float32)
    for k, (bx, by, bw) in enumerate(per_client):
        s_k = steps_per_client[k]
        px[k, :s_k], py[k, :s_k], pw[k, :s_k] = bx, by, bw
        pv[k, :s_k] = 1.0
    return CohortPlan(
        x=px, y=py, sample_w=pw, step_valid=pv,
        epochs=[max(1, int(e)) for e in epochs],
        num_samples=[len(x) for x, _ in client_data],
    )


def pad_plan_clients(plan: CohortPlan, multiple: int) -> CohortPlan:
    """Pad the client axis to a multiple of ``multiple`` (the mesh data-axis
    size) with all-invalid clients.

    A padded client has ``step_valid == 0`` everywhere, so every one of its
    scan steps is an exact no-op: its update row is identically zero and it
    is sliced off before the round's flat buffer is consumed.
    """
    from repro.core.distributed import pad_dim

    p = plan.num_clients
    p_pad = pad_dim(p, multiple)
    if p_pad == p:
        return plan

    def pad(a: np.ndarray) -> np.ndarray:
        return np.concatenate([a, np.zeros((p_pad - p, *a.shape[1:]), a.dtype)])

    return CohortPlan(
        x=pad(plan.x), y=pad(plan.y), sample_w=pad(plan.sample_w),
        step_valid=pad(plan.step_valid),
        epochs=list(plan.epochs) + [0] * (p_pad - p),
        num_samples=list(plan.num_samples) + [0] * (p_pad - p),
    )


def stack_variant_trees(trees: Sequence[Optional[PyTree]], template: PyTree) -> Tuple[Optional[PyTree], bool]:
    """Stack per-client mask pytrees along a new leading axis.

    ``None`` entries become all-ones (multiplying by 1.0 is exact in fp32, so
    clients without a mask are untouched).  Returns ``(stacked, any_present)``;
    when no client has a mask the stacked tree is ``None`` and the program
    skips masking entirely.
    """
    if all(tr is None for tr in trees):
        return None, False
    filled = [
        tr if tr is not None else jax.tree_util.tree_map(jnp.ones_like, template)
        for tr in trees
    ]
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *filled), True


def stack_freeze_flags(params: PyTree, freeze_fracs: Sequence[float]) -> PyTree:
    """Per-leaf trainability flags for a cohort: (P,)-stacked scalars."""
    flags = [_freeze_mask(params, float(f)) for f in freeze_fracs]
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *flags)


def steps_to_run(valid: jax.Array) -> jax.Array:
    """A cohort's trip count: over its (P, S) step-validity rows, the
    largest (index of a row's last real step + 1), as an int32 scalar; 0 for
    a cohort of padding.  Every step from there on is padding in every row.
    """
    ends = jnp.where(valid > 0, jnp.arange(1, valid.shape[-1] + 1, dtype=jnp.int32), 0)
    return jnp.max(ends, initial=0)


class BatchedCohortTrainer:
    """Runs all P selected clients' local epochs as one device program.

    The returned flat update matrix uses the same leaf order as
    :func:`repro.core.distributed.flatten_pytree`, so the engine can hand it
    straight to aggregation, relationship modeling, and early stopping
    without re-flattening.
    """

    def __init__(self, model, learning_rate: float, batch_size: int):
        self.model = model
        self.lr = learning_rate
        self.batch_size = batch_size
        # the (P, S) step-validity buffer is freshly uploaded each round and
        # never read after the program runs; donating it frees XLA to write
        # the same-shaped (P, S) loss-trace output into it in place.  (The
        # other plan tensors have no same-shaped output to alias, so
        # donating them would only trigger the not-usable warning.)
        self._train = jax.jit(
            self._make_train(),
            static_argnames=("use_prox", "has_mask"),
            donate_argnums=(4,),
        )

    def _make_train(self, load=None):
        """The cohort program: a loop over steps of the client-vmapped SGD
        step, run only to the cohort's last real step.

        The trip count is :func:`steps_to_run` of the (P, S) step validity:
        the steps after it are padding in every row (the bucket's slack),
        and each would be an exact no-op, so they are not run.  A row that
        ends sooner is gated by its validity, as before; the loss trace
        keeps its (P, S) shape and reads 0 at the steps not run.

        ``load`` maps one step's ``(xs, ys)`` entries to that step's
        ``(x, y)`` batch; ``None`` takes materialized batches.  The compiled
        chunks use it to restore the store's flat samples to the model's
        feature shape and, where the store is not split over devices, to
        gather each step's batch from it (scan_driver).

        A model that counts its experts' routed assignments
        (``expert_counts_shape``) adds a fourth output: each client's per-layer,
        per-expert count over its real samples and steps, (P, L, E).
        """
        model, lr = self.model, self.lr
        counts_shape = getattr(model, "expert_counts_shape", None)
        counting = counts_shape is not None

        def per_example_losses(p, x, y):
            # model.loss over a single-sample batch == that sample's loss;
            # vmap re-batches it, matching the sequential batched compute.
            if counting:
                return jax.vmap(lambda xi, yi: model.loss_and_counts(p, xi[None], yi[None]))(x, y)
            return jax.vmap(lambda xi, yi: model.loss(p, xi[None], yi[None]))(x, y)

        def step(params, global_params, x, y, w, v, mask, freeze, prox_mu, *, use_prox, has_mask):
            if load is not None:
                x, y = load(x, y)

            def loss_fn(p):
                q = jax.tree_util.tree_map(lambda a, m: a * m, p, mask) if has_mask else p
                per = per_example_losses(q, x, y)
                if counting:
                    per, counts = per
                    counts = jnp.sum(counts * (w > 0)[:, None, None].astype(counts.dtype), 0)
                base = jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)
                if use_prox:
                    # on the MASKED params, matching ClientTrainer's loss_fn
                    sq = sum(
                        jnp.sum(jnp.square(a - b))
                        for a, b in zip(
                            jax.tree_util.tree_leaves(q),
                            jax.tree_util.tree_leaves(global_params),
                        )
                    )
                    base = base + 0.5 * prox_mu * sq
                return (base, counts) if counting else base

            if counting:
                (loss, counts), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            else:
                loss, grads = jax.value_and_grad(loss_fn)(params)
            if has_mask:
                grads = jax.tree_util.tree_map(lambda g, m: g * m, grads, mask)
            # freeze flags and the step-validity flag both gate the update
            grads = jax.tree_util.tree_map(lambda g, f: g * (f * v), grads, freeze)
            new_params = jax.tree_util.tree_map(lambda a, g: a - lr * g, params, grads)
            if counting:
                return new_params, loss, counts * v
            return new_params, loss

        def train(global_params, xs, ys, ws, valid, mask, freeze, prox_mu, *, use_prox, has_mask):
            p, s = valid.shape
            cohort_step = jax.vmap(
                functools.partial(step, use_prox=use_prox, has_mask=has_mask),
                in_axes=(0, None, 0, 0, 0, 0, 0 if has_mask else None, 0, 0),
            )

            def body(i, carry):
                params, losses = carry[:2]
                x, y, w, v = (
                    jax.lax.dynamic_index_in_dim(a, i, axis=1, keepdims=False)
                    for a in (xs, ys, ws, valid)
                )
                out = cohort_step(
                    params, global_params, x, y, w, v, mask, freeze, prox_mu
                )
                params, loss = out[:2]
                losses = jax.lax.dynamic_update_index_in_dim(
                    losses, loss.astype(losses.dtype), i, axis=1
                )
                if counting:
                    return params, losses, carry[2] + out[2]
                return params, losses

            start = (
                jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (p, *a.shape)), global_params
                ),
                # the loss trace takes the validity's dtype (f32), so the
                # donated validity buffer still aliases it
                jnp.zeros((p, s), valid.dtype),
            )
            if counting:
                start = start + (jnp.zeros((p, *counts_shape), jnp.float32),)
            final = jax.lax.fori_loop(0, steps_to_run(valid), body, start)
            updates = tree_sub(final[0], global_params)
            if has_mask:
                updates = jax.tree_util.tree_map(lambda u, m: u * m, updates, mask)
            flat = jnp.concatenate(
                [jnp.reshape(l, (p, -1)).astype(jnp.float32)
                 for l in jax.tree_util.tree_leaves(updates)],
                axis=1,
            )
            return (updates, flat, *final[1:])

        return train

    def rebind(self, model) -> "BatchedCohortTrainer":
        """This trainer over ``model`` (a model bound to a chunk's traced
        frozen weights); programs built from it are not cached."""
        other = copy.copy(self)
        other.model = model
        for cache in ("_sharded_raw_cache", "_sharded_train_cache"):
            if hasattr(self, cache):
                setattr(other, cache, {})
        return other

    def train_cohort(
        self,
        global_params: PyTree,
        plan: CohortPlan,
        *,
        prox_mus: Sequence[float],
        masks: Sequence[Optional[PyTree]],
        freeze_fracs: Sequence[float],
    ) -> Tuple[PyTree, jax.Array, List[Dict[str, float]]]:
        """Returns (stacked update pytree with leading P axis,
        flat (P, D) fp32 update matrix, per-client stats)."""
        mask, has_mask = stack_variant_trees(masks, global_params)
        freeze = stack_freeze_flags(global_params, freeze_fracs)
        mu = jnp.asarray(np.asarray(prox_mus, np.float32))
        use_prox = bool(np.any(np.asarray(prox_mus) > 0.0))
        updates, flat, losses, *_ = self._train(
            global_params,
            jnp.asarray(plan.x),
            jnp.asarray(plan.y),
            jnp.asarray(plan.sample_w),
            jnp.asarray(plan.step_valid),
            mask if has_mask else {},
            freeze,
            mu,
            use_prox=use_prox,
            has_mask=has_mask,
        )
        stats = cohort_stats(np.asarray(losses), plan)
        return updates, flat, stats


class ShardedCohortTrainer(BatchedCohortTrainer):
    """BatchedCohortTrainer distributed over a ``(data, model)`` mesh.

    Local training shard_maps the SAME cohort program over the mesh
    ``data`` axis — each shard trains its slice of the (client-padded) cohort
    against the replicated global model, to its own rows' last real step
    (the step loop holds no collective) — and the resulting flat update
    matrix is resharded in one jitted step so D is split over EVERY mesh axis
    (zero-padded to the shard count), exactly the layout the sharded Gram
    reductions (aggregation, ingest, early stopping) consume.  The (P, D)
    buffer is never replicated and never bounces through the host.
    """

    def __init__(
        self,
        model,
        learning_rate: float,
        batch_size: int,
        mesh,
        *,
        data_axis: str = "data",
    ):
        super().__init__(model, learning_rate, batch_size)
        from repro.core.distributed import mesh_axes_size

        if data_axis not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no {data_axis!r} axis")
        self.mesh = mesh
        self.data_axis = data_axis
        self.axes = tuple(mesh.axis_names)
        self.num_shards = mesh_axes_size(mesh, self.axes)
        # model-axis composition: a model that publishes ``param_specs(mesh)``
        # (the sharding-policy layouts — e.g. LMClassifier) trains under GSPMD
        # partitioning with its params pinned model-sharded instead of
        # shard_map-replicated, so a model too big for one device still runs
        # sharded cohort rounds.  ``None`` keeps the replicated shard_map path.
        self.param_shardings = (
            model.param_specs(mesh) if hasattr(model, "param_specs") else None
        )
        self._sharded_raw_cache: Dict[Tuple[bool, bool], Any] = {}
        self._sharded_train_cache: Dict[Tuple[bool, bool], Any] = {}
        self._reshard_cache: Dict[Tuple[int, int, int], Any] = {}
        # cache telemetry: a round loop must resolve each program ONCE per
        # job key and hit the cache afterwards (tests/test_sharded_engine.py
        # asserts the hit counts — per-round rebuilds were the retrace churn
        # behind the pre-PR-5 sharded rounds/s)
        self.train_cache_hits = 0
        self.train_cache_misses = 0
        self.reshard_cache_hits = 0
        self.reshard_cache_misses = 0

    def _sharded_train_raw(self, use_prox: bool, has_mask: bool, load=None):
        """The bare mesh cohort program (not jitted) — the form the compiled
        round chunks trace straight into their scan body (with ``load``, see
        :meth:`_make_train`: it may close over the chunk's traced store, so
        that program is built per trace and not cached).

        Replicated-model path: shard_map the cohort program over ``data``
        (params replicated per shard).  Model-sharded path (the model
        publishes ``param_specs``): the SAME cohort program, partitioned by
        GSPMD instead — params pinned to the policy's (data, model) layouts,
        batch tensors pinned client-sharded over ``data`` — so the params are
        never materialized replicated on any device.
        """
        key = (use_prox, has_mask)
        if load is None and key in self._sharded_raw_cache:
            return self._sharded_raw_cache[key]
        counting = getattr(self.model, "expert_counts_shape", None) is not None
        train = functools.partial(
            self._make_train(load), use_prox=use_prox, has_mask=has_mask
        )
        if self.param_shardings is not None:
            program = self._gspmd_train(train)
        else:
            from jax.sharding import PartitionSpec as P
            from repro.core.distributed import _shard_map

            dspec = P(self.data_axis)
            in_specs = (P(), dspec, dspec, dspec, dspec, dspec, dspec, dspec)
            out_specs = (dspec, P(self.data_axis, None), dspec) + ((dspec,) if counting else ())
            program = _shard_map(train, self.mesh, in_specs, out_specs)
        if load is None:
            self._sharded_raw_cache[key] = program
        return program

    def _gspmd_train(self, train):
        """GSPMD-partitioned cohort training for a model-sharded model.

        ``with_sharding_constraint`` pins every param leaf to the sharding
        policy's layout and the (client-padded) plan tensors client-sharded
        over ``data``; XLA partitions the cohort program across the
        composed (data, model) mesh.  The trip count is read from the whole
        cohort's validity, so every device runs the same steps and the
        collectives inside a step stay matched.  The flat update matrix
        leaves in the shard_map path's row-sharded layout, so
        :meth:`reshard_rows_traced` and everything downstream are shared
        verbatim with the replicated path.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, da = self.mesh, self.data_axis
        pshard = self.param_shardings
        wsc = jax.lax.with_sharding_constraint

        def pin_rows(t: jax.Array) -> jax.Array:
            return wsc(t, NamedSharding(mesh, P(da, *([None] * (t.ndim - 1)))))

        def run(global_params, xs, ys, ws, valid, mask, freeze, prox_mu):
            gp = jax.tree_util.tree_map(wsc, global_params, pshard)
            xs, ys, ws, valid = (pin_rows(t) for t in (xs, ys, ws, valid))
            mask = jax.tree_util.tree_map(pin_rows, mask)
            prox_mu = pin_rows(prox_mu)
            updates, flat, *rest = train(
                gp, xs, ys, ws, valid, mask, freeze, prox_mu
            )
            return (updates, pin_rows(flat), *(pin_rows(r) for r in rest))

        return run

    def _sharded_train(self, use_prox: bool, has_mask: bool):
        key = (use_prox, has_mask)
        if key not in self._sharded_train_cache:
            self.train_cache_misses += 1
            self._sharded_train_cache[key] = jax.jit(
                self._sharded_train_raw(use_prox, has_mask),
                donate_argnums=(4,),
            )
        else:
            self.train_cache_hits += 1
        return self._sharded_train_cache[key]

    def reshard_rows_traced(self, flat: jax.Array, n_real: int) -> jax.Array:
        """The one pad-then-all-to-all reshard, as a traceable expression.

        Pad D under the producer's row sharding, reshard the evenly shaped
        matrix (a clean all-to-all), THEN slice the now-replicated client
        axis — letting XLA reshard the ragged unpadded input instead forces
        a full rematerialization.  Shared verbatim by the jitted per-round
        path (:meth:`shard_updates`) and the compiled chunk body
        (``repro.fl.scan_driver``), so the loop and scan reshards can never
        drift apart.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.distributed import pad_dim

        d = flat.shape[1]
        d_pad = pad_dim(d, self.num_shards)
        g = jnp.pad(flat, ((0, 0), (0, d_pad - d)))
        g = jax.lax.with_sharding_constraint(
            g, NamedSharding(self.mesh, P(self.data_axis, None))
        )
        g = jax.lax.with_sharding_constraint(
            g, NamedSharding(self.mesh, P(None, self.axes))
        )
        return g[:n_real]

    def _reshard_flat(self, n_real: int, d: int):
        """One jitted pad+reshard: drop padded clients, zero-pad D to the
        shard count, lay the matrix out D-sharded over every mesh axis."""
        from repro.core.distributed import pad_dim

        d_pad = pad_dim(d, self.num_shards)
        key = (n_real, d, d_pad)
        if key not in self._reshard_cache:
            self.reshard_cache_misses += 1
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(self.mesh, P(None, self.axes))
            self._reshard_cache[key] = jax.jit(
                lambda f: self.reshard_rows_traced(f, n_real),
                out_shardings=sharding,
            )
        else:
            self.reshard_cache_hits += 1
        return self._reshard_cache[key]

    def prepare_job(self, clients_per_round: int, dim: int) -> None:
        """Resolve the job's reshard program once, before the round loop.

        ``run_federated`` calls this at engine setup so the per-round
        ``shard_updates`` path is a pure cache hit; the train program still
        resolves on first use (its ``(use_prox, has_mask)`` key needs the
        round's configs) and is likewise a hit from round 2 on.
        """
        self._reshard_flat(clients_per_round, dim)

    def train_cohort(
        self,
        global_params: PyTree,
        plan: CohortPlan,
        *,
        prox_mus: Sequence[float],
        masks: Sequence[Optional[PyTree]],
        freeze_fracs: Sequence[float],
    ) -> Tuple[PyTree, jax.Array, List[Dict[str, float]]]:
        """Returns (stacked update pytree with a client-padded leading axis,
        flat (P, D_pad) fp32 update matrix D-sharded over the mesh,
        per-client stats for the REAL clients)."""
        n_data = self.mesh.shape[self.data_axis]
        p_real = plan.num_clients
        padded = pad_plan_clients(plan, n_data)
        n_pad = padded.num_clients - p_real
        mask, has_mask = stack_variant_trees(
            list(masks) + [None] * n_pad, global_params
        )
        freeze = stack_freeze_flags(
            global_params, list(freeze_fracs) + [0.0] * n_pad
        )
        mu = jnp.asarray(np.asarray(list(prox_mus) + [0.0] * n_pad, np.float32))
        use_prox = bool(np.any(np.asarray(prox_mus) > 0.0))
        train = self._sharded_train(use_prox, has_mask)
        updates, flat, losses, *_ = train(
            global_params,
            jnp.asarray(padded.x),
            jnp.asarray(padded.y),
            jnp.asarray(padded.sample_w),
            jnp.asarray(padded.step_valid),
            mask if has_mask else {},
            freeze,
            mu,
        )
        flat = self.shard_updates(flat, p_real)
        stats = cohort_stats(np.asarray(losses)[:p_real], plan)
        return updates, flat, stats

    def shard_updates(self, flat: jax.Array, n_real: int) -> jax.Array:
        """Lay a flat update matrix out in the round-buffer layout: the first
        ``n_real`` rows, D zero-padded to the shard count, D-sharded over
        every mesh axis (also used to re-shard host-processed columns)."""
        return self._reshard_flat(n_real, flat.shape[1])(flat)


def cohort_stats(losses: np.ndarray, plan: CohortPlan) -> List[Dict[str, float]]:
    """Per-client stats from the (P, S) loss trace — ONE host transfer/round."""
    out: List[Dict[str, float]] = []
    for k in range(plan.num_clients):
        v = plan.step_valid[k] > 0
        lk = losses[k][v]
        out.append({
            "mean_loss": float(np.mean(lk)) if lk.size else float("nan"),
            "final_loss": float(lk[-1]) if lk.size else float("nan"),
            "samples_processed": float(plan.sample_w[k].sum()),
            "steps": float(v.sum()),
        })
    return out
