"""Public entry points for every Pallas kernel.

Each resolves ``interpret=None`` from the detected JAX backend (see
:func:`repro.kernels.default_interpret`): compiled through Mosaic on TPU,
interpreted (the kernel body traces to XLA ops, validating the exact blocked
algorithm) on CPU/GPU.
"""
from __future__ import annotations

from repro.kernels.aggregate import weighted_aggregate
from repro.kernels.decode_attention import decode_attention
from repro.kernels.gram import cross_gram, gram
from repro.kernels.mla_attention import mla_attention
from repro.kernels.moe_gmm import gmm as moe_gmm
from repro.kernels.topk_mask import topk_mask, topk_mask_rows

__all__ = [
    "cross_gram",
    "decode_attention",
    "gram",
    "mla_attention",
    "moe_gmm",
    "topk_mask",
    "topk_mask_rows",
    "weighted_aggregate",
]
