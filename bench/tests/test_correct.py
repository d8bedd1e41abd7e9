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
from reference import Reference, leaf_sizes, make_init

CFG = {"name": "tiny", "side": 8, "channels": 1, "num_classes": 4, "num_fc": 2,
       "conv_channels": [4, 8], "conv_kernel": 5, "fc_width": 16}
MIX = {"clients": 8, "clients_per_round": 3, "local_epochs": 2, "batch_size": 4,
       "learning_rate": 0.01, "partition": "label", "alpha": 0.5, "num_samples": 200,
       "num_eval": 50, "noise": 0.7, "partition_seed": 0, "explore_decay": 0.5,
       "es_threshold": 1.0, "chunk_rounds": 2, "chunk_seconds": 1e9,
       "min_window_chunks": 2, "trace_chunks": 1, "check_rounds": 3}
# float32 against float32 on the CPU: gaps of 1e-6 and under, but for Eq. 6,
# whose dot-product form in the program loses digits to cancellation where
# a peer's ray runs nearly along w - a (gaps near 2e-4)
LIMITS = {"selection": 0, "es": 0, "loss": 1e-4, "accuracy": 0.02, "update": 1e-4,
          "model": 1e-4, "omega": 2e-3, "heuristic": 1e-2, "conflicts": 0,
          "aggregate": 1e-4, "omega_last": 2e-3}
SEED = 2**31 + 5


def cell():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return SimpleNamespace(bench=bench, cell={"name": "tiny.cell", "chips": 1},
                           cfg=CFG, mix=MIX, limits=LIMITS)


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
    counts = traffic.fixed_counts(CFG, MIX)
    data = traffic.make_traffic(CFG, MIX, counts, seeds["data"])
    init = make_init(CFG)
    sizes = leaf_sizes(init(seeds["init"]))
    want = Reference(CFG, MIX).run(data, init(seeds["init"]), seeds["select"], seeds["job"], 3)
    got = Reference(CFG, MIX, precision=precision, fault=fault).run(
        data, init(seeds["init"]), seeds["select"], seeds["job"], 3)
    nums = compare.numbers(got, want, sizes, 3, LIMITS, MIX["es_threshold"])
    assert not compare.judge(nums, LIMITS), nums
