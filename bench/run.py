#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one result line.

    python3 bench/run.py --workload emnist_cnn.paper --seed 7 --seconds 20 --trace 0

A cell (``workloads`` in ``BENCHMARK.json``) names a model configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/workloads/<traffic>.json``); its correctness limits are
``bench/limits/<cell>.json`` and each per-layer metric is read by
``bench/metrics/<metric>.py``.  The configuration's ``model`` key names
its model module, ``bench/models/<model>.py``, which gives all that is
particular to the architecture: the program's model object, the trained
and the frozen weights, the samples' features and number of classes, the
plain reference's loss and count of correct answers, and the model FLOPs a
sample.  All are found by name: a new cell, config, model or metric is new
files and ``BENCHMARK.json`` entries, and no shared harness file names a
model.

One process runs one federated job through the program's public entry,
``run_federated(model, data, FLrce(...), engine="sharded", driver="scan")``,
with Alg. 3 evaluated every round and never acted on (the paper's
"FLrce w/o ES" arm), so the job always runs its whole length.  The job is
``1 + W`` chunks: chunk 0 compiles (or loads the program from the
persistent cache) and warms up, and is set-up; the window is the other
``W`` chunks, timed from the flush that ends chunk 0 to the last flush
(the sum of the round records' flush-to-flush ``wall_s``).  ``W`` is the
least whole number of chunks that, at the mix's ``chunk_seconds``, lasts
``--seconds``, and at least the mix's ``min_window_chunks``.

``--trace 1`` runs the same job and traces the device from the build of
chunk 2 (just after chunk 0's flush, while chunk 1 runs) for the mix's
``trace_chunks`` chunks; it reports the per-layer metrics instead of the
end-to-end ones.

After the window the program's state is read and freed, and the plain
reference (``bench/reference.py``) follows the job's first rounds from the
same seed; ``bench/compare.py`` holds the two to the cell's limits, and
checks the window's last round against a recomputation from the state it
started from.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
from types import SimpleNamespace
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _path in (HERE, os.path.join(ROOT, "src")):     # the harness, the program
    if _path not in sys.path:
        sys.path.insert(0, _path)


class NoChip(RuntimeError):
    pass


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _load_file(path: str, module_name: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_model(name: str, directory: str = os.path.join(HERE, "models")):
    """The model module ``<directory>/<name>.py`` (see ``bench/models``)."""
    path = os.path.join(directory, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no model module {path}")
    return _load_file(path, f"bench_model_{name}")


def load_cell(name: str) -> SimpleNamespace:
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = cells[name]
    cfg = _json(os.path.join(HERE, "configs", f"{cell['config']}.json"))
    return SimpleNamespace(
        bench=bench,
        cell=cell,
        cfg=cfg,
        model=load_model(cfg["model"]),
        mix=_json(os.path.join(HERE, "workloads", f"{cell['traffic']}.json")),
        limits=_json(os.path.join(HERE, "limits", f"{name}.json"))["limits"],
    )


def applies(metric: Dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def derive_seeds(seed: int, mix: Optional[Dict] = None) -> Dict[str, int]:
    """Independent seeds for the data, the trained weights, FLrce's selection
    stream, the batch shuffles and the frozen weights, all from ``--seed``
    (any whole number); the selection stream is the mix's
    ``selection_seed`` where it has one (``bench/traffic.py``)."""
    import numpy as np

    ss = np.random.SeedSequence(abs(int(seed)), spawn_key=(int(seed < 0),))
    data, init, sel, job, frozen = ss.spawn(5)
    pick = lambda s: int(s.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)
    select = pick(sel)
    if mix is not None and "selection_seed" in mix:
        select = int(mix["selection_seed"])
    return {"data": data, "init": pick(init), "select": select, "job": pick(job),
            "frozen": pick(frozen)}


def window_chunks(mix: Dict, seconds: float) -> int:
    return max(int(mix["min_window_chunks"]),
               math.ceil(float(seconds) / float(mix["chunk_seconds"])))


def check_chip(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devices)}")
    return devices[0]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``, a fixed path."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileTimes:
    """Host times at which an XLA compile (or persistent-cache load) ended."""

    def __init__(self):
        import jax

        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.perf_counter())


def observe(res, strategy, rounds: int, dim: int) -> Dict:
    """What the program shows of its first ``rounds`` rounds and of the
    window's last round, in the form ``compare`` reads.  FLrce's server
    keeps, for every client, the round it was last active in, its update
    then, the global model it left from, its relationship row and H: a
    client last active in round t < ``rounds`` still holds that round's
    values, and one last active in round 1 <= t <= ``rounds`` shows the
    global model after t rounds.  The last round's cohort holds that
    round's values, and the final model is that round's aggregate."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    st = strategy.server.state
    last = np.asarray(st.last_round)
    v = np.asarray(st.updates)[:, :dim]
    a = np.asarray(st.anchors)[:, :dim]
    omega = np.asarray(st.omega)
    heur = np.asarray(st.heuristic)
    early = [int(c) for c in np.flatnonzero((last >= 0) & (last <= rounds))]
    out = {
        "rounds": [
            {"ids": list(r.selected), "exploited": bool(r.exploited),
             "loss": float(r.mean_client_loss), "accuracy": float(r.accuracy)}
            for r in res.records[:rounds]
        ],
        "updates": {}, "omega": {}, "heuristic": {}, "models": {},
        "es_stop_round": st.stop_round,
    }
    for c in early:
        t = int(last[c])
        if t < rounds:
            out["updates"][(t, c)] = v[c]
            out["omega"][(t, c)] = omega[c]
            out["heuristic"][(t, c)] = float(heur[c])
        out["models"].setdefault(t, a[c])
    rec = res.records[-1]
    ids = [int(c) for c in rec.selected]
    leaves = jax.tree_util.tree_leaves(res.final_params)
    out["last"] = {
        "t": int(rec.t), "ids": ids, "exploited": bool(rec.exploited),
        "v": v, "a": a, "r": last.astype(np.int64), "omega": omega[ids],
        "conflicts": float(st.last_conflicts),
        "w": np.concatenate([np.ravel(np.asarray(l, np.float32)) for l in leaves]),
    }
    return out


def run_cell(c: SimpleNamespace, seed: int, seconds: float, trace: bool, *,
             trace_dir: Optional[str] = None, t_process: float = T_PROCESS) -> Dict:
    """One run of one cell; returns the result line's object."""
    import jax

    from repro.fl import FLrce, run_federated
    from repro.data.synthetic import FederatedDataset
    from repro.launch import make_engine_mesh

    import compare
    import roofline
    import traffic as traffic_mod
    from reference import Reference, leaf_sizes, make_weights

    arch, cfg, mix, name = c.model, c.cfg, c.mix, c.cell["name"]
    seeds = derive_seeds(seed, mix)
    notes: List[str] = []

    # --- set-up: data, weights, the strategy ------------------------------
    t0 = time.perf_counter()
    counts = traffic_mod.fixed_counts(arch, cfg, mix)
    data = traffic_mod.make_traffic(arch, cfg, mix, counts, seeds["data"])
    ds = FederatedDataset(x=data.x, y=data.y, client_indices=data.client_indices,
                          eval_x=data.eval_x, eval_y=data.eval_y,
                          num_classes=arch.num_classes(cfg))
    t_data = time.perf_counter() - t0
    weights = make_weights(arch, cfg)
    params0, frozen = jax.block_until_ready(weights(seeds["init"], seeds["frozen"]))
    sizes = leaf_sizes(params0)                  # the trained parameters only
    dim = sum(sizes)
    model = arch.program_model(cfg, frozen)

    r_chunk = int(mix["chunk_rounds"])
    n_window = window_chunks(mix, seconds)
    max_rounds = r_chunk * (1 + n_window)
    check_rounds = int(mix["check_rounds"])
    trace_from, trace_to = 2 * r_chunk, (2 + int(mix["trace_chunks"])) * r_chunk
    tracing = {"on": False, "dir": None}
    built: Dict[int, float] = {}
    if trace:
        tracing["dir"] = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")

    def on_build(t_first: int) -> None:
        # the driver builds chunk k+2 just after flushing chunk k
        built.setdefault(t_first, time.perf_counter())
        if not trace:
            return
        if t_first == trace_from and not tracing["on"]:
            jax.profiler.start_trace(tracing["dir"])
            tracing["on"] = True
        elif t_first == trace_to and tracing["on"]:
            jax.profiler.stop_trace()
            tracing["on"] = None

    class WatchedFLrce(FLrce):
        """FLrce, told when the driver builds a chunk (the hook changes
        nothing: it returns FLrce's own proposal)."""

        def propose_candidates(self, ts):
            on_build(int(ts[0]))
            return super().propose_candidates(ts)

    p = int(mix["clients_per_round"])
    strategy = WatchedFLrce(
        num_clients=int(mix["clients"]), clients_per_round=p,
        local_epochs=int(mix["local_epochs"]), dim=dim,
        es_threshold=float(mix["es_threshold"]), explore_decay=float(mix["explore_decay"]),
        use_early_stopping=False, seed=seeds["select"],
    )
    chips = int(c.cell["chips"])
    mesh = make_engine_mesh(1, 1) if chips == 1 else make_engine_mesh()
    compiles = CompileTimes()

    # --- the job: chunk 0 is set-up, the rest the window --------------------
    res = run_federated(
        model, ds, strategy, max_rounds=max_rounds,
        learning_rate=float(mix["learning_rate"]), batch_size=int(mix["batch_size"]),
        seed=seeds["job"], init_params=params0, engine="sharded", mesh=mesh,
        driver="scan", scan_chunk_rounds=r_chunk,
    )
    t_end = time.perf_counter()
    if tracing["on"]:
        jax.profiler.stop_trace()
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))

    recs = res.records
    if len(recs) != max_rounds:
        raise RuntimeError(f"the job ran {len(recs)} of {max_rounds} rounds")
    win = recs[r_chunk:]
    window_s = sum(r.wall_s for r in win)
    # the driver's flush-to-flush clock starts as it builds chunk 0
    t_job0 = built[0]
    window_start = t_job0 + sum(r.wall_s for r in recs[:r_chunk])
    window_end = window_start + window_s
    setup_s = window_start - t_process
    round_s = window_s / len(win)
    in_window = sum(1 for t in compiles.times if window_start < t <= window_end)
    failed = sum(1 for r in win if not (math.isfinite(r.mean_client_loss)
                                        and math.isfinite(r.accuracy)))
    trained = sum(int(data.sizes[cid]) for r in win for cid in r.selected)
    flops_window = arch.flops_per_sample(cfg) * trained * int(mix["local_epochs"])
    longest = [max(traffic_mod.local_steps(int(data.sizes[cid]), int(mix["local_epochs"]),
                                           int(mix["batch_size"])) for cid in r.selected)
               for r in win]
    notes.append(
        f"set-up {setup_s:.3f} s: data {t_data:.3f} s, job start to chunk 0's flush "
        f"{window_start - t_job0:.3f} s, process start to job start "
        f"{t_job0 - t_process:.3f} s; window {window_s:.6f} s over {len(win)} rounds "
        f"({n_window} chunks of {r_chunk}); compiles in the window {in_window}; "
        f"driver compiles_chunk {res.driver_stats.get('compiles_chunk')}; model FLOPs "
        f"in the window {flops_window!r} over {trained} samples trained; each window "
        f"round's longest client, in local steps, {longest} ("
        f"{''.join('x' if r.exploited else 'e' for r in win)}: explore or exploit)"
    )

    # --- read the program's state, free it, run the reference ---------------
    prog = observe(res, strategy, check_rounds, dim)
    del res, strategy, model, params0, frozen
    gc.collect()
    params_ref, frozen_ref = weights(seeds["init"], seeds["frozen"])
    t_ref = time.perf_counter()
    ref = Reference(arch, cfg, mix).run(data, params_ref, frozen_ref, seeds["select"],
                                        seeds["job"], check_rounds)
    notes.append(f"reference {time.perf_counter() - t_ref:.3f} s over {check_rounds} rounds "
                 + " ".join(f"{k} {v:.3f} s" for k, v in ref["seconds"].items()))
    psi = float(mix["es_threshold"])
    nums = compare.numbers(prog, ref, sizes, check_rounds, c.limits, psi)
    t_win = time.perf_counter()
    nums.update(compare.window_numbers(prog["last"], sizes, data.sizes, psi))
    for t, (a, b) in enumerate(zip(prog["rounds"], ref["rounds"])):
        notes.append(f"round {t}: exploited {a['exploited']} reference {b['exploited']}; "
                     f"loss {a['loss']!r} reference {b['loss']!r}; "
                     f"accuracy {a['accuracy']!r} reference {b['accuracy']!r}")
    for g in compare.client_gaps(prog, ref, sizes, check_rounds):
        to = g["widest_to"]
        notes.append(f"client {g['client']} of {int(data.sizes[g['client']])} samples, round "
                     f"{g['round']}: update gap {g['update']!r}, Omega row widest "
                     f"{g['omega']!r} (to client {to} of "
                     f"{int(data.sizes[to]) if to >= 0 else 0} samples)")
    last = prog["last"]
    notes.append(f"compared: {len(prog['updates'])} first-round updates, "
                 f"models after rounds {sorted(prog['models'])}; window's last round "
                 f"{last['t']} ({'exploit' if last['exploited'] else 'explore'}, "
                 f"{last['conflicts'] * len(last['ids']):.0f} conflicting pairs) "
                 f"recomputed in {time.perf_counter() - t_win:.3f} s")
    correct = compare.judge(nums, c.limits) and failed == 0

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak_bytes}
    out: Dict = {"correct": bool(correct), "attempted": len(win), "failed": failed}
    metrics: Dict[str, Dict] = {}
    if not trace:
        values = {"round_s": round_s, "peak_hbm_gb": peak_bytes / 1e9, "setup_s": setup_s}
        for m in c.bench["end_to_end"]:
            if applies(m, name) and m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        import devtrace as trace_mod

        tr = trace_mod.load(tracing["dir"])
        try:
            peak = roofline.peaks(dev.device_kind)
        except KeyError:
            if dev.platform == "tpu":
                raise
            peak = None
        run = SimpleNamespace(
            trace=tr, cfg=cfg, mix=mix, peak=peak, round_s=round_s, window_s=window_s,
            model_flops_window=flops_window, notes=notes,
        )
        for m in c.bench["per_layer"]:
            if not applies(m, name):
                continue
            value = load_metric(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10)}
        if trace_dir is None:
            shutil.rmtree(tracing["dir"], ignore_errors=True)
    out["metrics"] = metrics
    out["device"] = device
    out["compiles_in_window"] = in_window
    out["checks"] = compare.report(nums, c.limits)
    out["notes"] = notes
    return out


def load_metric(name: str):
    metrics = os.path.join(HERE, "metrics")
    if metrics not in sys.path:
        sys.path.insert(0, metrics)
    return _load_file(os.path.join(metrics, f"{name}.py"), f"bench_metric_{name}")


def print_result(out: Dict) -> None:
    notes = out.pop("notes", [])
    checks = out.pop("checks")
    for line in notes:
        print(line, file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    out["checks"] = checks                       # the numbers compared, last
    print(json.dumps(out))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the trace in this directory (default: a temporary one)")
    args = ap.parse_args(argv)
    c = load_cell(args.workload)
    try:
        check_chip(int(c.cell["chips"]))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    use_compile_cache()
    out = run_cell(c, args.seed, args.seconds, bool(args.trace), trace_dir=args.trace_dir)
    print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
