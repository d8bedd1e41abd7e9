"""Federated traffic for the benchmark: one general generator, driven by the
parameters of a traffic mix (``bench/workloads/<traffic>.json``).

Clients hold Dirichlet label-skewed or quantity-skewed shards.  Both
partitions are copies of the program's own (``dirichlet_label_partition``,
``dirichlet_quantity_partition``), kept here so that a change to the
program's data code cannot move the yardstick.  The features of each label
are the model's to draw: its module (``bench/models/<model>.py``) states
``num_classes`` and gives a ``sampler``.

The per-client sample counts (and, for label skew, each client's class
counts) are a property of the mix: they are drawn once from the mix's
``partition_seed``, so every run of a cell trains on the same set of client
sizes and its padded step bucket never changes.  ``--seed`` draws
everything else: which client gets which shard, which label each class
count stands for, the sampler, every feature, the eval set, the initial
weights and FLrce's selection stream.

A mix with a ``selection_seed`` fixes the cohorts too: client i holds the
i-th counts, and FLrce's selection stream is seeded by ``selection_seed``
(``run.derive_seeds``), so every explore round trains the same clients
whatever ``--seed`` is, and a round, which runs to its cohort's longest
client, does the same work; ``--seed`` still draws the labels, features,
eval set, weights and batch order.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


def label_partition(labels: np.ndarray, num_clients: int, alpha: float,
                    seed: int, min_size: int = 2) -> List[np.ndarray]:
    """Dir_y(alpha) label skew: per-class proportions across clients."""
    rng = np.random.default_rng(seed)
    num_classes = int(labels.max()) + 1
    for _attempt in range(100):
        idx_by_client: List[list] = [[] for _ in range(num_clients)]
        for y in range(num_classes):
            idx_y = np.flatnonzero(labels == y)
            rng.shuffle(idx_y)
            props = rng.dirichlet(np.full(num_clients, alpha))
            cuts = (np.cumsum(props) * len(idx_y)).astype(int)[:-1]
            for client, part in enumerate(np.split(idx_y, cuts)):
                idx_by_client[client].extend(part.tolist())
        if min(len(ix) for ix in idx_by_client) >= min_size:
            break
    return [np.asarray(sorted(ix), dtype=np.int64) for ix in idx_by_client]


def quantity_sizes(num_samples: int, num_clients: int, alpha: float,
                   seed: int, min_size: int = 2) -> np.ndarray:
    """Dir(alpha) quantity skew: client sample counts (class-agnostic).

    The program's partition draws an index shuffle of ``num_samples``
    before the proportions, so its proportions change with the count.
    Here they are drawn first and alone: one draw per seed, which the
    counts then scale, so the largest client grows with ``num_samples``.
    """
    rng = np.random.default_rng(seed)
    props = rng.dirichlet(np.full(num_clients, alpha))
    base = min_size * num_clients
    if base > num_samples:
        raise ValueError("num_samples too small for min_size per client")
    sizes = min_size + (props * (num_samples - base)).astype(int)
    sizes[-1] += num_samples - int(sizes.sum())
    return sizes.astype(np.int64)


def fixed_counts(model, cfg: Dict, mix: Dict) -> np.ndarray:
    """The mix's per-client counts, drawn once from ``partition_seed``.

    Label skew: an (M, classes) matrix of class counts, drawn after the
    model's sampler as the program's generator draws them (for the CNN,
    those the program's ``make_image_like(seed=partition_seed)`` partition
    gives).  Quantity skew: an (M, 1) column of sample counts.
    """
    m, seed = int(mix["clients"]), int(mix["partition_seed"])
    if mix["partition"] == "quantity":
        return quantity_sizes(int(mix["num_samples"]), m, float(mix["alpha"]),
                              seed)[:, None]
    if mix["partition"] != "label":
        raise ValueError(f"unknown partition {mix['partition']!r}")
    k = model.num_classes(cfg)
    rng = np.random.default_rng(seed)
    model.sampler(cfg, mix, rng)
    labels = rng.integers(0, k, size=int(mix["num_samples"])).astype(np.int32)
    parts = label_partition(labels, m, float(mix["alpha"]), seed)
    return np.stack([np.bincount(labels[p], minlength=k) for p in parts])


@dataclasses.dataclass
class Traffic:
    """One run's data, as the harness hands it to the program."""

    x: np.ndarray                 # (N, ...) float32, the model's features
    y: np.ndarray                 # (N,) int32
    client_indices: List[np.ndarray]
    eval_x: np.ndarray
    eval_y: np.ndarray
    sizes: np.ndarray             # (M,) int64, client_indices' lengths


def make_traffic(model, cfg: Dict, mix: Dict, counts: np.ndarray,
                 seed_seq: np.random.SeedSequence) -> Traffic:
    """Draw one run's samples over the mix's fixed counts."""
    rng = np.random.default_rng(seed_seq)
    m, k = counts.shape[0], model.num_classes(cfg)
    if "selection_seed" in mix:                  # slot i's counts go to client
        client_of_slot = np.arange(m)
    else:
        client_of_slot = rng.permutation(m)
    class_of = rng.permutation(k)
    draw = model.sampler(cfg, mix, rng)
    by_client: List[np.ndarray] = [None] * m     # type: ignore[list-item]
    for slot in range(m):
        row = counts[slot]
        if counts.shape[1] == 1:
            labels = rng.integers(0, k, size=int(row[0]))
        else:
            labels = np.repeat(class_of, row)
        by_client[client_of_slot[slot]] = labels.astype(np.int32)
    sizes = np.asarray([len(l) for l in by_client], np.int64)
    y = np.concatenate(by_client)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    client_indices = [np.arange(s, s + n, dtype=np.int64)
                      for s, n in zip(starts, sizes)]
    eval_y = rng.integers(0, k, size=int(mix["num_eval"])).astype(np.int32)
    return Traffic(x=draw(y), y=y, client_indices=client_indices,
                   eval_x=draw(eval_y), eval_y=eval_y, sizes=sizes)


def step_bucket(steps: int) -> int:
    """The program's padded step axis for ``steps`` local steps: the next
    power of two, at least 8."""
    b = 8
    while b < max(steps, 1):
        b <<= 1
    return b


def local_steps(n: int, epochs: int, batch_size: int) -> int:
    return epochs * -(-n // batch_size)
