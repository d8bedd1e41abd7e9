"""Pallas TPU kernels for the FLrce compute hot-spots.

gram.py             pairwise Gram matrix (relationship modeling, Eq. 5 / Alg. 3)
aggregate.py        fused weighted aggregation (Eq. 4)
topk_mask.py        block-local magnitude sparsification (Fedcom baseline)
decode_attention.py flash-decoding GQA attention (serving shapes)
moe_gmm.py          grouped expert products (dropless MoE)
ops.py              public entry points
ref.py              pure-jnp oracles

Every kernel entry point takes ``interpret=None``, which resolves through
:func:`default_interpret`: compiled through Mosaic on TPU, interpreted on
every other backend.  A TPU never runs a kernel interpreted.
"""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Compile through Mosaic only on TPU; interpret everywhere else.

    These kernels carry TPU compiler params (and TPU memory spaces), so only
    the TPU backend can compile them; on CPU/GPU the interpreter — which
    still jit-lowers to XLA and validates the exact blocked algorithm — is
    the correct default.
    """
    return jax.default_backend() != "tpu"
