"""Pallas TPU kernels for the FLrce compute hot-spots.

gram.py             pairwise Gram matrix (relationship modeling, Eq. 5 / Alg. 3)
aggregate.py        fused weighted aggregation (Eq. 4)
topk_mask.py        block-local magnitude sparsification (Fedcom baseline)
decode_attention.py flash-decoding GQA attention (serving shapes)
moe_gmm.py          grouped expert products (dropless MoE)
mla_attention.py    causal flash attention at MLA's head sizes (training)
ops.py              public entry points
ref.py              pure-jnp oracles

Every kernel entry point takes ``interpret=None``, which resolves through
:func:`default_interpret`: compiled through Mosaic on TPU, interpreted on
every other backend.  A TPU never runs a kernel interpreted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def default_interpret() -> bool:
    """Compile through Mosaic only on TPU; interpret everywhere else.

    These kernels carry TPU compiler params (and TPU memory spaces), so only
    the TPU backend can compile them; on CPU/GPU the interpreter — which
    still jit-lowers to XLA and validates the exact blocked algorithm — is
    the correct default.
    """
    return jax.default_backend() != "tpu"


def fold_vmap(impl, n_folded: int):
    """``impl`` as a ``custom_vmap`` whose first ``n_folded`` args have a
    leading batch (token) axis and whose other args are shared (weights):
    a mapped call folds the mapped axis into the leading axis, each mapped
    (V, N, ...) arg becoming (V·N, ...) and an unmapped one broadcast first,
    and runs as one kernel call.  Shared args may not be mapped."""
    fn = jax.custom_batching.custom_vmap(impl)

    @fn.def_vmap
    def rule(axis_size, in_batched, *args):
        if any(in_batched[n_folded:]):
            raise NotImplementedError("per-client weights under vmap")
        folded = [a if bat else jnp.broadcast_to(a, (axis_size, *a.shape))
                  for a, bat in zip(args[:n_folded], in_batched)]
        out = fn(*[a.reshape(axis_size * a.shape[1], *a.shape[2:]) for a in folded],
                 *args[n_folded:])
        outs = out if isinstance(out, tuple) else (out,)
        unfolded = tuple(o.reshape(axis_size, -1, *o.shape[1:]) for o in outs)
        if not isinstance(out, tuple):
            return unfolded[0], True
        return unfolded, tuple(True for _ in outs)

    return fn
