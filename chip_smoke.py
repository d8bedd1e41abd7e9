#!/usr/bin/env python3
"""Smoke test of FLrce's compiled round on a TPU.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # (2, 2) mesh vs one device, four chips

One chip: checks the Pallas kernels against their jnp oracles on small
inputs, then runs the paper's CIFAR-10 job through
``run_federated(..., engine="sharded", driver="scan")``: ``PaperCNN`` at full
width (D = 595,914), M = 100 clients, P = 10 per round, five local epochs,
Dir(0.1) label skew over 50,000 synthetic 32x32x3 images, two chunks of four
rounds.  It prints every round and the compile and memory numbers, and fails
when a loss or accuracy is not finite, when the final accuracy is not above
chance, or when the chunk program compiled (or loaded from the persistent
cache) other than exactly once.

``--four-chips`` runs only the mesh phase: the same job on
``make_engine_mesh()`` (a (2, 2) mesh on four chips) and on one device with
``engine="batched"``, and checks that FLrce's V/A maps are sharded over all
four devices.  It then asserts the two jobs equivalent with
``tests/equivalence.py``: selections, flags and ledger exactly, accuracy and
loss at its fp32 tolerances or, past those, within the job's noise floor —
the largest per-round gap between the one-device job and its twin started
from every initial weight moved one ulp up.

Exits non-zero, printing no result line, when JAX finds no TPU.  The last
line of a passing run is one JSON object with ``"ok": true`` and the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

M, P, EPOCHS = 100, 10, 5
ROUNDS, CHUNK = 8, 4
NUM_SAMPLES, NUM_EVAL = 50_000, 10_000
LEARNING_RATE, BATCH = 0.01, 32
CHANCE = 0.1


class CompileClock:
    """Seconds spent compiling XLA programs or loading them from the
    persistent compilation cache (one backend-compile event covers either),
    and the programs' names, summed over the process."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name="?", **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs.append(fun_name)

    def report(self, label, start):
        """Print the programs compiled since ``start`` (an index)."""
        from collections import Counter

        names = Counter(self.programs[start:])
        print(f"[{label}] programs compiled or loaded: {sum(names.values())} "
              + " ".join(f"{n}x{c}" for n, c in names.most_common()))


def check_kernels():
    """Mosaic kernels against the ref.py oracles at small, ragged shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(10, 5000)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(700, 5000)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(5000,)), jnp.float32)
    p = jnp.asarray(rng.dirichlet(np.ones(10)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        pairs = {
            "gram": (ops.gram(u), ref.gram_ref(u)),
            "cross_gram": (ops.cross_gram(u, v), ref.cross_gram_ref(u, v)),
            "weighted_aggregate": (
                ops.weighted_aggregate(w, u, p), ref.weighted_aggregate_ref(w, u, p)
            ),
        }
    for name, (got, want) in pairs.items():
        got, want = np.asarray(got), np.asarray(want)
        err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        print(f"kernel {name}: shape={got.shape} max_err/max={err:.3e}")
        if not err < 1e-2:
            raise AssertionError(f"{name} disagrees with its oracle: {err}")
    got = np.asarray(ops.topk_mask_rows(u, keep_frac=0.1))
    want = np.stack([np.asarray(ref.topk_mask_ref(r, keep_frac=0.1)) for r in u])
    print(f"kernel topk_mask_rows: bitwise={np.array_equal(got, want)}")
    if not np.array_equal(got, want):
        raise AssertionError("topk_mask_rows is not bitwise its oracle")


def make_job():
    import jax

    from repro.data.synthetic import make_image_like
    from repro.models.cnn import PaperCNN, param_count

    model = PaperCNN(side=32, channels=3, num_classes=10, num_fc=3)
    ds = make_image_like(
        num_clients=M, alpha=0.1, num_samples=NUM_SAMPLES, num_eval=NUM_EVAL,
        side=32, channels=3, num_classes=10, seed=0,
    )
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    return model, ds, dim


def run_job(model, ds, dim, **kw):
    from repro.fl import FLrce, run_federated

    strategy = FLrce(
        num_clients=M, clients_per_round=P, local_epochs=EPOCHS, dim=dim,
        es_threshold=P / 2, explore_decay=0.98, seed=0,
    )
    res = run_federated(
        model, ds, strategy, max_rounds=ROUNDS, learning_rate=LEARNING_RATE,
        batch_size=BATCH, seed=0, driver="scan", scan_chunk_rounds=CHUNK, **kw,
    )
    return res, strategy


def report(res, label):
    """Print the job's rounds; return the problems found."""
    problems = []
    for r in res.records:
        print(
            f"[{label}] round {r.t}: selected={r.selected} acc={r.accuracy:.4f} "
            f"loss={r.mean_client_loss:.4f} stopped={r.stopped}"
        )
        if not (math.isfinite(r.accuracy) and math.isfinite(r.mean_client_loss)):
            problems.append(f"round {r.t}: non-finite accuracy or loss")
    compiles = res.driver_stats.get("compiles_chunk")
    print(f"[{label}] rounds_run={res.rounds_run} final_accuracy={res.final_accuracy:.4f} "
          f"stopped_early={res.stopped_early} compiles_chunk={compiles}")
    if not res.final_accuracy > CHANCE:
        problems.append(f"final accuracy {res.final_accuracy} not above chance")
    if compiles != 1:
        problems.append(f"compiles_chunk={compiles}, expected 1")
    return problems


def one_chip(clock):
    import jax

    check_kernels()
    model, ds, dim = make_job()
    print(f"job: PaperCNN D={dim} M={M} P={P} epochs={EPOCHS} "
          f"rounds={ROUNDS} chunk={CHUNK}")
    c0, n0, t0 = clock.seconds, len(clock.programs), time.perf_counter()
    res, _ = run_job(model, ds, dim, engine="sharded")
    wall = time.perf_counter() - t0
    problems = report(res, "sharded")
    clock.report("sharded", n0)
    steady = [r.wall_s for r in res.records[CHUNK:]]
    per_round = f"{sum(steady) / len(steady):.6f}" if steady else "not measured"
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"compile_s={clock.seconds - c0:.3f} job_wall_s={wall:.3f} "
          f"steady_s_per_round={per_round} peak_bytes_in_use={peak}")
    st = res.driver_stats
    print("driver_stats: " + " ".join(
        f"{k}={st[k]}" for k in ("setup_store_s", "host_build_s", "dispatch_s",
                                 "compile_s", "device_wait_s", "host_flush_s",
                                 "steps_useful", "steps_executed", "steps_bucket",
                                 "schedule_bytes_host", "chunks")))
    return problems


def four_chips(clock):
    import jax
    import numpy as np

    from repro.launch import make_engine_mesh

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from equivalence import assert_runs_equivalent

    if jax.device_count() != 4:
        return [f"--four-chips needs 4 devices, found {jax.device_count()}"]
    model, ds, dim = make_job()
    mesh = make_engine_mesh()
    print(f"mesh: {dict(mesh.shape)}")
    # compared at fp32 tolerances, so both jobs run fp32 matmuls (the TPU
    # default for an f32 matmul is one bf16 pass)
    with jax.default_matmul_precision("highest"):
        n0 = len(clock.programs)
        res_mesh, strat = run_job(model, ds, dim, engine="sharded", mesh=mesh)
        problems = report(res_mesh, "mesh")
        clock.report("mesh", n0)
        peaks = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]
        print(f"[mesh] peak_bytes_in_use per device: {peaks}")
        maps = {id(a): a for a in (strat.server.state.updates, strat.server.state.anchors)}
        live = [a for a in jax.live_arrays() if id(a) in maps]
        for a in live:
            shard = {tuple(s.data.shape) for s in a.addressable_shards}
            print(f"V/A map {a.shape}: devices={len(a.sharding.device_set)} "
                  f"shard_shapes={sorted(shard)} spec={a.sharding.spec}")
            if len(a.sharding.device_set) != 4 or shard != {(M, a.shape[1] // 4)}:
                problems.append(f"V/A map {a.shape} is not D-sharded over 4 devices")
        if len(live) != 2:
            problems.append(f"found {len(live)} of the 2 V/A maps among live arrays")
        del strat, maps, live
        n0 = len(clock.programs)
        res_one, _ = run_job(model, ds, dim, engine="batched")
        problems += report(res_one, "one-device")
        clock.report("one-device", n0)
        # the job's own noise: the one-device job again, from every initial
        # weight moved one ulp up
        p0 = model.init(jax.random.PRNGKey(0))
        p1 = jax.tree_util.tree_map(
            lambda a: np.nextafter(np.asarray(a), np.float32(np.inf)), p0
        )
        res_ulp, _ = run_job(model, ds, dim, engine="batched", init_params=p1)
        problems += report(res_ulp, "one-ulp twin")
    print("mesh vs one-device:")
    print_gaps(res_mesh, res_one)
    print("one-ulp twin vs one-device:")
    acc_noise, loss_noise = print_gaps(res_ulp, res_one)
    print(f"noise floor: |d acc|<={acc_noise!r} |d loss|<={loss_noise!r}")
    try:
        assert_runs_equivalent(res_mesh, res_one, bitwise=False)
        print("mesh job equivalent to the one-device job at the fp32 tolerances")
    except AssertionError as e:
        # Different programs drift apart through ~500 SGD steps per client
        # per round, as a one-ulp change of the initial weights does: past
        # the fp32 tolerances, the mesh job must stay within that noise.
        print(f"mesh job outside the fp32 tolerances: {e}".strip())
        try:
            assert_runs_equivalent(
                res_mesh, res_one, bitwise=False,
                accuracy_atol=max(2e-3, acc_noise), loss_abs=max(1e-4, loss_noise),
            )
            print("mesh job equivalent to the one-device job within its noise floor")
        except AssertionError as e2:
            problems.append(
                f"mesh job differs from the one-device job beyond its noise: {e2}"
            )
    diff = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(jax.tree_util.tree_leaves(res_mesh.final_params),
                        jax.tree_util.tree_leaves(res_one.final_params))
    )
    print(f"mesh vs one-device: max |final param diff|={diff:.3e}")
    return problems


def print_gaps(a, b):
    """Print two jobs' per-round gaps; return the largest accuracy and loss
    gaps over the rounds before their selections first differ."""
    acc = loss = 0.0
    same = True
    for ra, rb in zip(a.records, b.records):
        same = same and ra.selected == rb.selected
        d_acc = abs(ra.accuracy - rb.accuracy)
        d_loss = abs(ra.mean_client_loss - rb.mean_client_loss)
        print(f"  round {ra.t}: same_selection={ra.selected == rb.selected} "
              f"|d acc|={d_acc!r} |d loss|={d_loss!r}")
        if same:
            acc, loss = max(acc, d_acc), max(loss, d_loss)
    return acc, loss


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (2, 2) mesh phase against one device")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform={dev.platform!r})", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import compile_cache

    print(f"device: {dev.platform} {dev.device_kind} x{jax.device_count()}; "
          f"compile cache: {compile_cache()}")
    clock = CompileClock()
    problems = four_chips(clock) if args.four_chips else one_chip(clock)
    for p in problems:
        print(f"FAIL: {p}", file=sys.stderr)
    if problems:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count(),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
