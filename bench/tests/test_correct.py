"""``correct`` comes out false when the timed path is broken, and the
control (the reference in bfloat16) fails the comparison.

A tiny cell on the CPU: the harness's run without its look for a chip,
the program's cohort trainer broken underneath by a patch.
"""
import json
import os
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

import compare
import run
import traffic
from reference import Reference, leaf_sizes, make_weights

CFG = {"name": "tiny", "model": "PaperCNN", "side": 8, "channels": 1, "num_classes": 4,
       "num_fc": 2, "conv_channels": [4, 8], "conv_kernel": 5, "fc_width": 16}
MIX = {"clients": 8, "clients_per_round": 3, "local_epochs": 2, "batch_size": 4,
       "learning_rate": 0.01, "partition": "label", "alpha": 0.5, "num_samples": 200,
       "num_eval": 50, "noise": 0.7, "partition_seed": 0, "explore_decay": 0.5,
       "es_threshold": 1.0, "chunk_rounds": 2, "chunk_seconds": 1e9,
       "min_window_chunks": 2, "trace_chunks": 1, "check_rounds": 3}
# float32 against float32 on the CPU: gaps of 1e-6 and under, but for Eq. 6,
# whose dot-product form in the program loses digits to cancellation where
# a peer's ray runs nearly along w - a (gaps near 2e-4)
LIMITS = {"selection": 0, "es": 0, "loss": 1e-4, "accuracy": 0.02, "update0": 1e-4,
          "update": 1e-4, "model": 1e-4, "omega0": 2e-3, "omega": 2e-3, "heuristic": 1e-2,
          "conflicts": 0, "aggregate": 1e-4, "omega_last": 2e-3}
SEED = 2**31 + 5


def cell():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return SimpleNamespace(bench=bench, cell={"name": "tiny.cell", "chips": 1},
                           cfg=CFG, model=run.load_model(CFG["model"]), mix=MIX,
                           limits=LIMITS)


def break_trainer(monkeypatch, fault):
    """Patch the program's cohort trainer, under the compiled chunk."""
    from repro.fl.client import ShardedCohortTrainer

    orig = ShardedCohortTrainer._sharded_train_raw

    def broken(self, use_prox, has_mask, load=None):
        train = orig(self, use_prox, has_mask, load)

        def wrapped(params, x, y, sw, sv, *rest):
            if fault == "half_batch":
                b = sw.shape[-1]
                sw = sw * (jnp.arange(b) < b // 2).astype(sw.dtype)
            upd, flat, losses = train(params, x, y, sw, sv, *rest)
            if fault == "unchanged":
                flat = jnp.zeros_like(flat)
            elif fault == "altered":
                flat = flat.at[0].multiply(1.01)
            return upd, flat, losses

        return wrapped

    monkeypatch.setattr(ShardedCohortTrainer, "_sharded_train_raw", broken)


def break_server(monkeypatch, fault):
    """Patch the program's server steps, under the compiled chunk."""
    import control
    import repro.core.distributed as distributed
    import repro.fl.scan_driver as scan_driver

    if fault == "ingest_unchanged":
        monkeypatch.setattr("repro.core.relationship.rows_from_relationship_dots",
                            control.unchanged_rows)
    elif fault == "aggregate_unchanged":
        monkeypatch.setattr(scan_driver, "sharded_aggregate",
                            lambda w, u, weights, mesh, axes: w)
    elif fault == "conflicts_altered":
        orig = distributed.conflict_pairs_from_gram
        monkeypatch.setattr(distributed, "conflict_pairs_from_gram",
                            lambda gram: orig(gram) + 2.0)


def test_sound_run_is_correct():
    out = run.run_cell(cell(), SEED, 1.0, False)
    assert out["correct"], out["checks"]
    assert out["compiles_in_window"] == 0
    # the window's last round is read in every run
    for name in ("conflicts", "aggregate", "omega_last"):
        assert out["checks"][name]["value"] is not None, name


@pytest.mark.parametrize("fault", ["ingest_unchanged", "aggregate_unchanged",
                                   "conflicts_altered"])
def test_broken_server_is_not_correct(monkeypatch, fault):
    break_server(monkeypatch, fault)
    out = run.run_cell(cell(), SEED + 1, 1.0, False)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    break_trainer(monkeypatch, fault)
    out = run.run_cell(cell(), SEED, 1.0, False)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("precision,fault", [("bfloat16", None), (None, "half_batch")])
def test_control_is_not_correct(precision, fault):
    """The reference in bfloat16, or with half of each batch left out, in
    the program's place."""
    seeds = run.derive_seeds(SEED)
    model = run.load_model(CFG["model"])
    counts = traffic.fixed_counts(model, CFG, MIX)
    data = traffic.make_traffic(model, CFG, MIX, counts, seeds["data"])
    params, frozen = make_weights(model, CFG)(seeds["init"], seeds["frozen"])
    sizes = leaf_sizes(params)
    want = Reference(model, CFG, MIX).run(data, params, frozen, seeds["select"], seeds["job"], 3)
    got = Reference(model, CFG, MIX, precision=precision, fault=fault).run(
        data, params, frozen, seeds["select"], seeds["job"], 3)
    nums = compare.numbers(got, want, sizes, 3, LIMITS, MIX["es_threshold"])
    assert not compare.judge(nums, LIMITS), nums


def test_loss_reads_the_rounds_after_the_first():
    """Round 0's loss gap is read as ``loss0`` and left out of ``loss``."""
    rounds = [{"ids": [0, 1], "exploited": False, "loss": v, "accuracy": 1.0}
              for v in (3.0, 0.5, 0.25)]
    prog = {"rounds": [{**r, "loss": v} for r, v in zip(rounds, (6.0, 0.55, 0.25))],
            "updates": {}, "omega": {}, "heuristic": {}, "models": {}, "es_stop_round": None}
    ref = {"rounds": rounds, "updates": {}, "omega": {}, "heuristic": {},
           "models": {0: np.zeros(2)}}
    nums = compare.numbers(prog, ref, [2], 3, LIMITS, MIX["es_threshold"])
    assert nums["loss0"] == pytest.approx(1.0)
    assert nums["loss"] == pytest.approx(0.1)
    one = compare.numbers({**prog, "rounds": prog["rounds"][:1]}, ref, [2], 1, LIMITS,
                          MIX["es_threshold"])
    assert one["loss0"] == pytest.approx(1.0) and one["loss"] is None


def test_update_and_omega_read_the_rounds_after_the_first():
    """A round-0 client's update and Omega row are read as ``update0`` and
    ``omega0`` and left out of ``update`` and ``omega``."""
    d = 4
    rounds = [{"ids": [0, 1], "exploited": False, "loss": 1.0, "accuracy": 1.0},
              {"ids": [2], "exploited": False, "loss": 1.0, "accuracy": 1.0}]
    ref = {"rounds": rounds, "models": {t: np.full(d, t) for t in range(3)},
           "updates": {}, "omega": {}, "heuristic": {}}
    prog = {"rounds": rounds, "models": dict(ref["models"]), "updates": {}, "omega": {},
            "heuristic": {}, "es_stop_round": None}
    for t, c, off in ((0, 0, 2.0), (0, 1, 0.0), (1, 2, 0.01)):
        row = np.asarray([1.0, 0.5, 0.25])
        ref["updates"][(t, c)], ref["omega"][(t, c)] = np.ones(d), row
        prog["updates"][(t, c)], prog["omega"][(t, c)] = np.ones(d) * (1 + off), row + off / 4
        ref["heuristic"][(t, c)] = prog["heuristic"][(t, c)] = 1.0
    nums = compare.numbers(prog, ref, [d], 2, LIMITS, MIX["es_threshold"])
    assert nums["update0"] == pytest.approx(2.0)
    assert nums["update"] == pytest.approx(0.01)
    assert nums["omega0"] == pytest.approx(0.5)
    assert nums["omega"] == pytest.approx(0.0025)
