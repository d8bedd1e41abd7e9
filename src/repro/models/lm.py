"""Transformer LMs behind the FL classifier protocol.

:class:`LMClassifier` wraps :class:`repro.models.transformer.TransformerLM`
(any ``ArchConfig`` from ``repro.configs``) so the federated engines — which
speak the ``ClassifierModel`` protocol of ``loss(params, x, y)`` over
``(N, *feat)`` float arrays — can train a language model without a special
code path.  The dataset convention (see
:func:`repro.data.lm.make_federated_lm`):

* ``x``      — ``(N, L)`` float32 **token ids** (exact for vocab < 2**24;
               the FL data substrate stacks float32 feature tensors)
* ``y``      — ``(N,)`` int32: the next token after the sequence (so the
               final-position prediction doubles as a classification target)

``loss`` supervises every next-token position — labels are
``[x[1:], y]`` — and ``accuracy`` is top-1 at the final position against
``y``, which keeps both methods drop-in for the engines' eval plumbing.

The wrapper exposes ``param_specs(mesh)`` delegating to
``repro.sharding.policy``: when the sharded engines see it, cohort training
runs GSPMD-partitioned with the params pinned to the policy's ``(data,
model)`` layout instead of shard_map-replicated — the model-axis composition
that lets a model too big for one device run sharded(-scan) rounds.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import moe as moe_mod
from repro.models.transformer import TransformerLM


@dataclasses.dataclass(frozen=True)
class LMClassifier:
    """``TransformerLM`` as a federated classifier model.

    ``seq_len`` is the dataset's fixed sequence length — used only by the
    analytic ``flops_per_sample`` the resource ledger charges (6·N·L for
    fwd+bwd, active params for MoE).
    """

    cfg: ArchConfig
    seq_len: int
    remat: bool = True
    name: str = "lm"
    # score ``accuracy`` over the rows in blocks of this many (0: all at
    # once), so an eval set need not fit one forward pass
    eval_batch: int = 0

    @property
    def lm(self) -> TransformerLM:
        return TransformerLM(self.cfg, remat=self.remat)

    def init(self, rng: jax.Array):
        return self.lm.init(rng)

    def _tokens(self, x: jax.Array) -> jax.Array:
        # token ids ride in the float32 feature tensor; exact below 2**24
        return x.astype(jnp.int32)

    def _batch(self, x: jax.Array, y: jax.Array):
        tokens = self._tokens(x)
        labels = jnp.concatenate(
            [tokens[:, 1:], y[:, None].astype(jnp.int32)], axis=1
        )
        return {"tokens": tokens, "labels": labels}

    def loss(self, params, x: jax.Array, y: jax.Array) -> jax.Array:
        return self.lm.loss(params, self._batch(x, y))

    @property
    def expert_counts_shape(self):
        """(layers, experts) of :meth:`loss_and_counts`' counts of routed
        assignments where the model has a dropless MoE, else None."""
        if self.cfg.moe is None or not moe_mod.is_dropless(self.cfg.moe,
                                                             self.lm.moe_capacity_factor):
            return None
        return (self.cfg.num_layers, self.cfg.moe.num_experts)

    def loss_and_counts(self, params, x: jax.Array, y: jax.Array):
        """``loss`` and each layer's per-expert count of routed assignments."""
        return self.lm.loss_and_counts(params, self._batch(x, y))

    def _hits(self, params, x: jax.Array, y: jax.Array) -> jax.Array:
        lm = self.lm
        h, _ = lm.hidden(params, {"tokens": self._tokens(x)})
        logits = lm.unembed(params, h[:, -1, :])
        return jnp.sum((jnp.argmax(logits, axis=-1) == y).astype(jnp.float32))

    def accuracy(self, params, x: jax.Array, y: jax.Array) -> jax.Array:
        n, eb = x.shape[0], self.eval_batch
        if not eb or n <= eb or n % eb:
            return self._hits(params, x, y) / n
        hits = jax.lax.map(lambda xy: self._hits(params, *xy),
                           (x.reshape(n // eb, eb, *x.shape[1:]), y.reshape(n // eb, eb)))
        return jnp.sum(hits) / n

    def flops_per_sample(self) -> float:
        # 6·N FLOPs/token for fwd+bwd (2N fwd, 4N bwd), active params for MoE
        return 6.0 * self.cfg.active_param_count() * self.seq_len

    def param_specs(self, mesh):
        """Policy ``NamedSharding`` tree for this model's params on ``mesh``.

        The sharded trainers pin the cohort program's params (and the eval
        params inside the compiled chunk) to these layouts, composing the
        model axis with the FL ``data`` axis.  Leaves whose dims do not
        divide the mesh fall back to replicated inside the policy.
        """
        from repro.sharding.policy import param_shardings

        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return param_shardings(shapes, mesh)
