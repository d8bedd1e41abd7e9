"""The harness's generator reproduces each mix's fixed client sizes."""
import json
import os

import numpy as np
import pytest

import run
import traffic

BENCH = os.path.dirname(traffic.__file__)


def load(kind, name):
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("config,mix,n_max,steps,bucket", [
    # the program's make_image_like(seed=0) sizes: N_max 3,510, 5 epochs
    ("cifar10_cnn", "cifar10_paper", 3510, 550, 1024),
    # the most samples whose fixed draw keeps N_max <= 6,528: 204 batches x 5
    ("emnist_cnn", "emnist_paper", 6528, 1020, 1024),
])
def test_fixed_sizes(config, mix, n_max, steps, bucket):
    cfg, m = load("configs", config), load("workloads", mix)
    counts = traffic.fixed_counts(run.load_model(cfg["model"]), cfg, m)
    sizes = counts.sum(axis=1)
    assert len(sizes) == m["clients"]
    assert sizes.sum() == m["num_samples"]
    assert sizes.max() == n_max
    assert traffic.local_steps(n_max, m["local_epochs"], m["batch_size"]) == steps
    assert traffic.step_bucket(steps) == bucket


def test_emnist_paper_is_the_largest_cut():
    m = load("workloads", "emnist_paper")
    big = traffic.quantity_sizes(m["num_samples"] + 1, m["clients"], m["alpha"],
                                 m["partition_seed"])
    assert big.max() > 6528


def test_cifar_counts_are_the_programs():
    from repro.data.synthetic import make_image_like

    cfg, m = load("configs", "cifar10_cnn"), load("workloads", "cifar10_paper")
    counts = traffic.fixed_counts(run.load_model(cfg["model"]), cfg, m)
    ds = make_image_like(num_clients=100, alpha=0.1, num_samples=50_000, num_eval=10,
                         side=32, channels=3, num_classes=10, seed=0)
    for k, ix in enumerate(ds.client_indices):
        assert np.array_equal(np.bincount(ds.y[ix], minlength=10), counts[k])


def test_seed_moves_samples_not_sizes():
    cfg = {"model": "PaperCNN", "side": 8, "channels": 1, "num_classes": 4}
    m = {"clients": 6, "partition": "label", "alpha": 0.5, "num_samples": 120,
         "partition_seed": 0, "num_eval": 10, "noise": 0.7}
    model = run.load_model(cfg["model"])
    counts = traffic.fixed_counts(model, cfg, m)
    a = traffic.make_traffic(model, cfg, m, counts, np.random.SeedSequence(1))
    b = traffic.make_traffic(model, cfg, m, counts, np.random.SeedSequence(2))
    again = traffic.make_traffic(model, cfg, m, counts, np.random.SeedSequence(1))
    assert sorted(a.sizes) == sorted(b.sizes) == sorted(counts.sum(axis=1))
    assert not np.array_equal(a.x, b.x)
    assert np.array_equal(a.x, again.x) and np.array_equal(a.sizes, again.sizes)


def test_selection_seed_fixes_the_cohorts():
    """With the mix's selection_seed every --seed gives each client the same
    count and FLrce the same selection stream; the samples still move."""
    cfg = load("configs", "emnist_cnn")
    m = {**load("workloads", "emnist_paper"), "num_samples": 1200, "num_eval": 20}
    assert "selection_seed" in load("workloads", "emnist_paper")
    model = run.load_model(cfg["model"])
    counts = traffic.fixed_counts(model, cfg, m)
    one, two = run.derive_seeds(2**31 + 11, m), run.derive_seeds(2**31 + 12, m)
    a = traffic.make_traffic(model, cfg, m, counts, one["data"])
    b = traffic.make_traffic(model, cfg, m, counts, two["data"])
    assert np.array_equal(a.sizes, counts[:, 0]) and np.array_equal(b.sizes, a.sizes)
    assert not np.array_equal(a.y, b.y) and not np.array_equal(a.x, b.x)
    assert one["select"] == two["select"] == m["selection_seed"]
    assert one["init"] != two["init"] and one["job"] != two["job"]
    free = {k: v for k, v in m.items() if k != "selection_seed"}
    assert run.derive_seeds(2**31 + 11, free)["select"] != run.derive_seeds(2**31 + 12, free)["select"]
    c = traffic.make_traffic(model, cfg, free, counts, one["data"])
    assert sorted(c.sizes) == sorted(a.sizes) and not np.array_equal(c.sizes, a.sizes)
