"""The yardstick's arithmetic: kernel operations and bytes computed from
shapes, and the chip's published peaks.  A model's FLOPs a sample are its
module's (``bench/models/<model>.py``).

Nothing here reads the program: a change to the program's own FLOP or
roofline helpers cannot move these numbers.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

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
