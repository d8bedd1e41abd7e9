"""Federated traffic for the benchmark: one general generator, driven by the
parameters of a traffic mix (``bench/workloads/<traffic>.json``).

Samples are synthetic class-conditional images: Gaussian class centres
pushed through a frozen random two-layer teacher, reshaped to
``(side, side, channels)``.  Clients hold Dirichlet label-skewed or
quantity-skewed shards.  The generator and both partitions are copies of
the program's own (``make_classification``, ``dirichlet_label_partition``,
``dirichlet_quantity_partition``, ``make_image_like``), kept here so that a
change to the program's data code cannot move the yardstick.

The per-client sample counts (and, for label skew, each client's class
counts) are a property of the mix: they are drawn once from the mix's
``partition_seed``, so every run of a cell trains on the same set of client
sizes and its padded step bucket never changes.  ``--seed`` draws
everything else: which client gets which shard, which label each class
count stands for, the teacher, every feature, the eval set, the initial
weights and FLrce's selection stream.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

TEACHER_HIDDEN = 64


def _teacher_draw(rng: np.random.Generator, feature_dim: int, num_classes: int):
    """The teacher, drawn and scaled as ``make_classification`` draws it."""
    w1 = rng.normal(size=(feature_dim, TEACHER_HIDDEN)) / np.sqrt(feature_dim)
    w2 = rng.normal(size=(TEACHER_HIDDEN, feature_dim)) / np.sqrt(TEACHER_HIDDEN)
    centers = rng.normal(size=(num_classes, feature_dim)) * 1.8
    return w1, w2, centers


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


def fixed_counts(cfg: Dict, mix: Dict) -> np.ndarray:
    """The mix's per-client counts, drawn once from ``partition_seed``.

    Label skew: an (M, classes) matrix of class counts, those the program's
    ``make_image_like(seed=partition_seed)`` partition gives.  Quantity
    skew: an (M, 1) column of sample counts.
    """
    m, seed = int(mix["clients"]), int(mix["partition_seed"])
    if mix["partition"] == "quantity":
        return quantity_sizes(int(mix["num_samples"]), m, float(mix["alpha"]),
                              seed)[:, None]
    if mix["partition"] != "label":
        raise ValueError(f"unknown partition {mix['partition']!r}")
    k = int(cfg["num_classes"])
    rng = np.random.default_rng(seed)
    _teacher_draw(rng, feature_dim(cfg), k)
    labels = rng.integers(0, k, size=int(mix["num_samples"])).astype(np.int32)
    parts = label_partition(labels, m, float(mix["alpha"]), seed)
    return np.stack([np.bincount(labels[p], minlength=k) for p in parts])


def feature_dim(cfg: Dict) -> int:
    return int(cfg["side"]) ** 2 * int(cfg["channels"])


@dataclasses.dataclass
class Traffic:
    """One run's data, as the harness hands it to the program."""

    x: np.ndarray                 # (N, side, side, channels) float32
    y: np.ndarray                 # (N,) int32
    client_indices: List[np.ndarray]
    eval_x: np.ndarray
    eval_y: np.ndarray
    sizes: np.ndarray             # (M,) int64, client_indices' lengths


def make_traffic(cfg: Dict, mix: Dict, counts: np.ndarray,
                 seed_seq: np.random.SeedSequence) -> Traffic:
    """Draw one run's samples over the mix's fixed counts."""
    rng = np.random.default_rng(seed_seq)
    m, k = counts.shape[0], int(cfg["num_classes"])
    client_of_slot = rng.permutation(m)          # slot i's counts go to client
    class_of = rng.permutation(k)
    w1, w2, centers = (a.astype(np.float32) for a in
                       _teacher_draw(rng, feature_dim(cfg), k))
    noise = float(mix["noise"])
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
    shape = (-1, int(cfg["side"]), int(cfg["side"]), int(cfg["channels"]))

    def draw(labels):
        z = centers[labels]
        z += noise * rng.standard_normal(z.shape, dtype=np.float32)
        x = np.tanh(z @ w1) @ w2
        x += 0.1 * z
        return x.reshape(shape)

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
