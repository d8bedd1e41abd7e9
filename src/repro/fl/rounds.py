"""The federated round engine (paper Algorithm 4's outer loop, strategy-agnostic).

Runs T rounds of: select → broadcast → local train → upload → aggregate →
strategy bookkeeping (RM + ES for FLrce) → evaluate, with exact resource
accounting through a :class:`ResourceLedger`.

Three interchangeable execution engines (see DESIGN.md §Engine):

* ``engine="sequential"`` — the reference path: one jitted SGD step per
  client per batch, driven from Python.  O(P × steps) device dispatches.
* ``engine="batched"`` — the single-device production path (default): the
  whole cohort's local training is one jitted program (a step loop over the
  client-vmapped SGD step), and the round's flat (P, D) update matrix is
  produced on device and shared — without bouncing through NumPy — between
  aggregation (Eq. 4), relationship modeling (Eq. 5/6 via the Gram
  kernels), and early stopping (Alg. 3).
* ``engine="sharded"`` — the batched program shard_mapped over a
  ``(data, model)`` mesh: cohort training splits over the ``data`` axis and
  the flat (P, D) buffer stays D-sharded over every mesh axis through
  aggregation, ingest and early stopping (the sharded Gram reductions in
  ``core.distributed``) — no replicated (P, D) materialization.

Every engine draws each client's batches from the same placement-independent
fold-in stream (``client_batch_rng``) and runs the same math, so all three
produce matching results within fp32 tolerance (tests/test_batched_engine.py,
tests/test_sharded_engine.py).

Orthogonally to the engine, ``driver`` picks how Algorithm 4's OUTER loop
executes:

* ``driver="loop"`` (default) — one Python iteration per round, one host
  sync per round.  Works with every engine and strategy.
* ``driver="scan"`` — whole chunks of rounds compile into one ``lax.scan``
  program over a device-resident, donated carry; the host syncs once per
  chunk (``repro.fl.scan_driver``).  By default the chunk loop is pipelined
  (``pipeline=True``): the next chunk is built, transferred and dispatched
  while the current chunk executes, hiding the host flush behind device
  compute; ``pipeline=False`` is the strictly serial chunk loop with
  bitwise-identical results.  Composes with ``engine="batched"``
  (the fused single-device path) and ``engine="sharded"`` (the same chunk
  with the body shard_mapped over the mesh and every O(D) buffer D-sharded
  across rounds).  Requires a strategy with ``supports_scan`` — FLrce and
  every §4.1 baseline except PyramidFL (whose selection depends on round
  results) — and, for the sharded chunks, ``supports_sharded_scan``
  (FLrce, FedAvg, Fedprox); see docs/support-matrix.md.

``client_store`` picks where the scan driver keeps the client universe:
``"resident"`` (default) uploads the stacked (M, N_max, …) store to the
device once; ``"paged"`` keeps it in host memory and pages only each chunk's
candidate rows — device memory O(P_cand) flat in M, bitwise-identical
results with full-universe candidates (``repro.data.HostClientStore``).
Scan-only: the loop drivers reject it rather than silently ignoring the
memory contract.

Update post-processing (Fedcom top-k, QuantizedFL int8) is a device-resident
``Strategy.update_transform`` applied to the round's flat (P, D) update
matrix by every engine — per-client updates never bounce through host NumPy.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import flatten_pytree, pad_dim, sharded_aggregate
from repro.data.synthetic import FederatedDataset
from repro.fl.aggregation import aggregation_weights
from repro.fl.client import (
    BatchedCohortTrainer,
    ClientTrainer,
    ShardedCohortTrainer,
    build_cohort_plan,
    client_batch_rng,
)
from repro.fl.metrics import ResourceLedger, communication_efficiency, computation_efficiency
from repro.fl.strategy import LocalConfig, Strategy
from repro.models.cnn import param_count

PyTree = Any

ENGINES = ("sequential", "batched", "sharded")
DRIVERS = ("loop", "scan")


@dataclasses.dataclass
class RoundRecord:
    t: int
    accuracy: float
    mean_client_loss: float
    energy_kj: float
    bytes_gb: float
    selected: List[int]
    exploited: bool
    stopped: bool
    wall_s: float
    evaluated: bool = True   # False ⇒ ``accuracy`` is copied from the last
    # freshly evaluated round (eval_every > 1), not a measurement of round t.


@dataclasses.dataclass
class FLResult:
    strategy: str
    records: List[RoundRecord]
    final_accuracy: float
    rounds_run: int
    stopped_early: bool
    ledger: ResourceLedger
    final_params: PyTree
    # driver-internal timing/counters (scan driver: chunk counts, speculative
    # dispatches, host-build/device-wait/host-flush split); empty for "loop"
    driver_stats: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def energy_kj(self) -> float:
        return self.ledger.energy_j / 1e3

    @property
    def bytes_gb(self) -> float:
        return self.ledger.total_bytes / 1e9

    @property
    def computation_efficiency(self) -> float:
        return computation_efficiency(self.final_accuracy, self.ledger.energy_j)

    @property
    def communication_efficiency(self) -> float:
        return communication_efficiency(self.final_accuracy, self.ledger.total_bytes)

    def accuracy_curve(self) -> np.ndarray:
        return np.asarray([r.accuracy for r in self.records])

    def summary(self) -> Dict[str, float]:
        return {
            "strategy": self.strategy,
            "final_accuracy": self.final_accuracy,
            "rounds": self.rounds_run,
            "stopped_early": self.stopped_early,
            "energy_kj": self.energy_kj,
            "bytes_gb": self.bytes_gb,
            "comp_eff": self.computation_efficiency,
            "comm_eff": self.communication_efficiency,
        }


def _flatten_update(update: PyTree) -> jax.Array:
    return flatten_pytree(update)[0]


def nan_safe_mean(values: Sequence[float]) -> float:
    """Mean over the finite entries; NaN only when EVERY entry is NaN.

    A zero-step client (empty shard, or epochs × batches == 0) reports
    ``mean_loss = NaN``; plain ``np.mean`` would poison the whole round's
    record.  ``np.nanmean`` semantics, minus its all-NaN RuntimeWarning.
    """
    vals = np.asarray(list(values), np.float64)
    finite = vals[~np.isnan(vals)]
    return float(finite.mean()) if finite.size else float("nan")


def finalize_result(
    *,
    strategy: Strategy,
    records: List[RoundRecord],
    stopped: bool,
    ledger: ResourceLedger,
    final_params: PyTree,
    driver_stats: Optional[Dict[str, Any]] = None,
) -> FLResult:
    """Assemble the FLResult shared by the loop and scan drivers.

    The terminal round (stop or ``max_rounds``) is always freshly evaluated,
    so the last evaluated record exists whenever any round ran; the explicit
    0.0 fallback covers the (validated-against) empty-records case instead
    of letting ``next()`` raise ``StopIteration``.
    """
    final_accuracy = next(
        (r.accuracy for r in reversed(records) if r.evaluated), 0.0
    )
    return FLResult(
        strategy=strategy.name,
        records=records,
        final_accuracy=final_accuracy,
        rounds_run=len(records),
        stopped_early=stopped,
        ledger=ledger,
        final_params=final_params,
        driver_stats=driver_stats or {},
    )


def _sequential_round(
    trainer: ClientTrainer,
    params: PyTree,
    dataset: FederatedDataset,
    ids: np.ndarray,
    cfgs: Sequence[LocalConfig],
    rngs: Sequence[np.random.Generator],
) -> Tuple[List[PyTree], List[Dict[str, float]]]:
    """Reference path: per-client Python loop over jitted single steps."""
    updates, stats = [], []
    for cid, cfg, rng_k in zip(ids, cfgs, rngs):
        x_k, y_k = dataset.client_data(int(cid))
        update, st = trainer.local_update(
            params,
            x_k,
            y_k,
            cfg.epochs,
            rng_k,
            prox_mu=cfg.prox_mu,
            mask=cfg.mask,
            freeze_frac=cfg.freeze_frac,
        )
        updates.append(update)
        stats.append(st)
    return updates, stats


def run_federated(
    model,
    dataset: FederatedDataset,
    strategy: Strategy,
    *,
    max_rounds: int = 100,
    learning_rate: float = 0.05,
    batch_size: int = 32,
    device: str = "jetson_nano",
    eval_every: int = 1,
    seed: int = 0,
    init_params: Optional[PyTree] = None,
    verbose: bool = False,
    engine: str = "batched",
    mesh=None,
    driver: str = "loop",
    scan_chunk_rounds: int = 8,
    pipeline: Optional[bool] = None,
    client_store: str = "resident",
    async_rounds: Optional["AsyncConfig"] = None,
) -> FLResult:
    if engine not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
    if driver not in DRIVERS:
        raise ValueError(f"driver must be one of {DRIVERS}, got {driver!r}")
    if max_rounds < 1:
        raise ValueError(f"max_rounds must be >= 1, got {max_rounds}")
    if pipeline is not None and driver != "scan":
        raise ValueError(
            "pipeline= selects the scan driver's chunk pipelining; it has no "
            f"meaning for driver={driver!r} (pass driver='scan')"
        )
    if client_store not in ("resident", "paged"):
        raise ValueError(
            f"client_store must be 'resident' or 'paged', got {client_store!r}"
        )
    if client_store == "paged" and driver != "scan":
        raise ValueError(
            "client_store='paged' is the scan driver's host-paged store; it "
            f"has no meaning for driver={driver!r} (pass driver='scan')"
        )
    if getattr(model, "param_subset", False) and not strategy.supports_param_subset:
        # adapter-style models train a parameter SUBSET; a strategy whose
        # variants presume the full vector (dropout masks, depth-indexed
        # freezing) would silently operate on meaningless coordinates
        reason = getattr(strategy, "param_subset_reason", None)
        raise ValueError(
            f"{strategy.name} does not support param-subset models like "
            f"{getattr(model, 'name', type(model).__name__)} "
            "(supports_param_subset is False"
            + (f": {reason}" if reason else "")
            + "); see docs/writing-a-strategy.md"
        )
    if async_rounds is not None:
        from repro.fl.async_rounds import AsyncConfig

        if not isinstance(async_rounds, AsyncConfig):
            raise ValueError(
                f"async_rounds must be an AsyncConfig, got "
                f"{type(async_rounds).__name__}"
            )
        async_rounds.validate(len(dataset.client_indices))
        if driver != "scan":
            raise ValueError(
                "async_rounds runs staleness-aware rounds on the compiled "
                f"chunk driver; it has no meaning for driver={driver!r} "
                "(pass driver='scan')"
            )
        if not getattr(strategy, "supports_async", False):
            raise ValueError(
                f"{strategy.name} does not support async_rounds "
                "(supports_async is False); see docs/support-matrix.md"
            )
        if client_store != "resident":
            raise ValueError(
                "async_rounds requires client_store='resident': a pending "
                "cohort's page would be gone by its landing chunk"
            )
    if driver == "scan":
        if engine == "sequential":
            raise ValueError(
                "driver='scan' compiles the batched or sharded engines; "
                f"engine='sequential' is the per-step reference loop (got "
                f"engine={engine!r}, use 'batched')"
            )
        compiled = strategy.supports_scan and (
            engine != "sharded" or strategy.supports_sharded_scan
        )
        if compiled:
            from repro.fl.scan_driver import run_scan_driver

            if engine == "sharded" and mesh is None:
                from repro.launch.mesh import make_engine_mesh

                mesh = make_engine_mesh()
            return run_scan_driver(
                model, dataset, strategy,
                max_rounds=max_rounds, learning_rate=learning_rate,
                batch_size=batch_size, device=device, eval_every=eval_every,
                seed=seed, init_params=init_params, verbose=verbose,
                chunk_rounds=scan_chunk_rounds,
                mesh=mesh if engine == "sharded" else None,
                # pipelining is ON by default: overlap the next chunk's
                # build/H2D/dispatch with the current chunk's execution
                pipeline=True if pipeline is None else pipeline,
                paged=client_store == "paged",
                async_rounds=async_rounds,
            )
        if async_rounds is not None:
            # the loop fallback has no arrival buffer — silently running it
            # synchronously would fabricate a staleness experiment
            raise ValueError(
                f"async_rounds requires the compiled scan path, but "
                f"{strategy.name} falls back to the {engine} loop driver "
                "(supports_scan/supports_sharded_scan)"
            )
        if client_store == "paged":
            # the loop drivers rebuild per-round cohort plans and never touch
            # a client store at all — a silent fallback would quietly ignore
            # the memory contract the caller asked for
            raise ValueError(
                f"client_store='paged' requires the compiled scan path, but "
                f"{strategy.name} falls back to the {engine} loop driver "
                "(supports_scan/supports_sharded_scan)"
            )
        # host-coupled per-round logic (PyramidFL's loss-driven selection) or
        # a strategy without the mesh-chunk contract (masks/freeze flags,
        # update transforms): fall back to the matching loop engine, which
        # handles every strategy
        if verbose:
            print(
                f"[{strategy.name}] no scan support for engine={engine!r}; "
                f"falling back to the {engine} loop driver"
            )
    params = init_params if init_params is not None else model.init(jax.random.PRNGKey(seed))
    n_params = param_count(params)
    # the strategy's device-resident update post-processing stage (Fedcom
    # top-k, QuantizedFL int8); jitted once, applied to the round's flat
    # (P, D) buffer by every engine.  The matrix argument is donated: the
    # transformed matrix aliases the incoming buffer in place (the engine
    # rebinds and never reads the pre-transform updates again).
    transform = strategy.update_transform(params)
    apply_transform = (
        jax.jit(transform, donate_argnums=(2,)) if transform is not None else None
    )
    trainer: Any
    shard_vec = None
    if engine == "sequential":
        trainer = ClientTrainer(model, learning_rate, batch_size)
    elif engine == "batched":
        trainer = BatchedCohortTrainer(model, learning_rate, batch_size)
    else:
        if mesh is None:
            from repro.launch.mesh import make_engine_mesh

            mesh = make_engine_mesh()
        trainer = ShardedCohortTrainer(model, learning_rate, batch_size, mesh)
        # resolve the job's reshard program once, outside the round loop —
        # every per-round shard_updates call is then a pure cache hit
        trainer.prepare_job(strategy.p, n_params)
        # strategies with O(D) state (FLrce's V/A maps) move it onto the mesh
        strategy.bind_mesh(mesh, trainer.axes)
        # the round's (D,) broadcast snapshot: zero-padded to the shard count
        # and laid out D-sharded, once per round, shared by aggregation and
        # post_round exactly like the dense engines share w_before
        from jax.sharding import NamedSharding, PartitionSpec

        d_pad = pad_dim(n_params, trainer.num_shards)
        shard_vec = jax.jit(
            lambda v: jnp.pad(v, (0, d_pad - n_params)),
            out_shardings=NamedSharding(mesh, PartitionSpec(trainer.axes)),
        )
    ledger = ResourceLedger(device=device)
    eval_fn = jax.jit(model.accuracy)
    eval_x, eval_y = jnp.asarray(dataset.eval_x), jnp.asarray(dataset.eval_y)
    sizes = dataset.client_sizes()
    records: List[RoundRecord] = []
    stopped = False
    last_eval_acc = 0.0

    for t in range(max_rounds):
        # monotonic clock: wall_s must never go negative under NTP slew
        t0 = time.perf_counter()
        ids = strategy.select(t)
        # The round's flat buffer: w_before is flattened ONCE and shared by
        # aggregation, relationship modeling, and early stopping.
        w_before, unflatten = flatten_pytree(params)
        cfgs = [strategy.client_config(t, int(cid), params) for cid in ids]
        # placement-independent batch randomness: one fold-in stream per
        # (seed, round, client) — identical across all three engines and
        # across any client→shard placement
        rngs = [client_batch_rng(seed, t, int(cid)) for cid in ids]

        if engine == "sequential":
            updates, stats = _sequential_round(trainer, params, dataset, ids, cfgs, rngs)
            update_matrix = jnp.stack([_flatten_update(u) for u in updates])
        else:
            plan = build_cohort_plan(
                [dataset.client_data(int(cid)) for cid in ids],
                [cfg.epochs for cfg in cfgs],
                batch_size,
                rngs,
            )
            _, update_matrix, stats = trainer.train_cohort(
                params,
                plan,
                prox_mus=[cfg.prox_mu for cfg in cfgs],
                masks=[cfg.mask for cfg in cfgs],
                freeze_fracs=[cfg.freeze_frac for cfg in cfgs],
            )

        # --- device-resident update transform (compression) -----------------
        if apply_transform is not None:
            update_matrix = apply_transform(
                jnp.int32(t), jnp.asarray(ids, jnp.int32), update_matrix
            )
            if engine == "sharded":
                # restore the D-sharded round-buffer layout
                update_matrix = trainer.shard_updates(update_matrix, len(ids))

        # --- resource accounting (fractions are static per-config metadata) -
        for cid, cfg in zip(ids, cfgs):
            flops = (
                model.flops_per_sample() * int(sizes[int(cid)]) * cfg.epochs * cfg.compute_fraction
            )
            ledger.charge_training(flops)
            ledger.charge_download(n_params, cfg.download_fraction)
            ledger.charge_upload(n_params, cfg.upload_fraction)

        # --- Eq. 4 aggregation from the shared flat buffer ------------------
        weights = jnp.asarray(aggregation_weights(sizes[ids]), jnp.float32)
        if engine == "sharded":
            # w and U stay D-sharded through aggregation AND post_round;
            # unflatten never reads the zero-padded tail
            w_before = shard_vec(w_before)
            params = unflatten(
                sharded_aggregate(w_before, update_matrix, weights, mesh, trainer.axes)
            )
        else:
            params = unflatten(w_before + weights @ update_matrix)

        # post_round receives DEVICE arrays: no host bounce between
        # aggregation, relationship modeling, and early stopping.
        stop = strategy.post_round(t, w_before, ids, update_matrix, stats)
        ledger.end_round()

        evaluated = (t % eval_every == 0) or stop or (t == max_rounds - 1)
        if evaluated:
            acc = float(eval_fn(params, eval_x, eval_y))
            last_eval_acc = acc
        else:
            acc = last_eval_acc
        rec = RoundRecord(
            t=t,
            accuracy=acc,
            mean_client_loss=nan_safe_mean([s["mean_loss"] for s in stats]),
            energy_kj=ledger.energy_j / 1e3,
            bytes_gb=ledger.total_bytes / 1e9,
            selected=[int(c) for c in ids],
            exploited=strategy.last_round_was_exploit,
            stopped=bool(stop),
            wall_s=time.perf_counter() - t0,
            evaluated=evaluated,
        )
        records.append(rec)
        if verbose:
            print(
                f"[{strategy.name}] round {t:3d} acc={acc:.4f} "
                f"loss={rec.mean_client_loss:.4f} stop={stop}"
            )
        if stop:
            stopped = True
            break

    return finalize_result(
        strategy=strategy,
        records=records,
        stopped=stopped,
        ledger=ledger,
        final_params=params,
    )
