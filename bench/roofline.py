"""The yardstick's arithmetic: model FLOPs, kernel operations and bytes
computed from shapes, and the chip's published peaks.

Nothing here reads the program: a change to the program's own FLOP or
roofline helpers cannot move these numbers.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> Dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(table)}); add them to bench/peaks.json"
        )
    return table[device_kind]


def _cnn_dims(cfg: Dict) -> Tuple[int, int, int, int, List[int]]:
    s, c = int(cfg["side"]), int(cfg["channels"])
    c1, c2 = (int(v) for v in cfg["conv_channels"])
    k = int(cfg["conv_kernel"])
    flat = (s // 4) * (s // 4) * c2
    fc = [flat] + [int(cfg["fc_width"])] * (int(cfg["num_fc"]) - 1) + [int(cfg["num_classes"])]
    return s, c, c1, c2, k, fc


def cnn_param_count(cfg: Dict) -> int:
    """Weights and biases of the 2-conv + fc CNN."""
    s, c, c1, c2, k, fc = _cnn_dims(cfg)
    conv = (k * k * c * c1 + c1) + (k * k * c1 * c2 + c2)
    return conv + sum(a * b + b for a, b in zip(fc[:-1], fc[1:]))


def cnn_flops_per_sample(cfg: Dict) -> float:
    """Model FLOPs to train on one sample: the forward pass's
    multiply-adds (2 FLOPs each) of both SAME-padded convolutions and the
    fc stack, times 3 for forward plus backward.  Pooling, activations,
    biases and the loss are left out, as is any padding the program adds."""
    s, c, c1, c2, k, fc = _cnn_dims(cfg)
    conv1 = 2 * s * s * k * k * c * c1
    conv2 = 2 * (s // 2) * (s // 2) * k * k * c1 * c2
    dense = sum(2 * a * b for a, b in zip(fc[:-1], fc[1:]))
    return 3.0 * (conv1 + conv2 + dense)


def cross_gram_cost(k: int, m: int, d: int, itemsize: int = 4,
                    in_hbm: Tuple[bool, bool, bool] = (True, True, True)) -> Tuple[float, float]:
    """Operations and HBM bytes that ``cross_gram(U (k, d), V (m, d))``
    needs: ``2 k m d`` FLOPs, and each of U, V and the (k, m) result read
    or written once, counted where ``in_hbm`` says it lives in HBM (an
    operand XLA placed in on-chip memory costs no HBM traffic here)."""
    sizes = (k * d, m * d, k * m)
    return 2.0 * k * m * d, float(itemsize) * sum(n for n, h in zip(sizes, in_hbm) if h)


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / float(peak["bf16_flops_per_s"])
    t_bytes = nbytes / float(peak["hbm_bytes_per_s"])
    return (t_bytes, "bandwidth") if t_bytes >= t_flops else (t_flops, "compute")
