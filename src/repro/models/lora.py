"""LoRA-style adapter fine-tuning as a first-class federated *model*.

:class:`LoRAClassifier` wraps any classifier model (MLP/CNN/``LMClassifier``)
so that **only low-rank adapter factors are trained, aggregated and
transmitted**: the wrapped model's parameters are frozen, ``init`` returns
the adapter pytree, and every ``loss``/``accuracy`` call evaluates the base
model with each target weight replaced by its :class:`Factored` form, whose
projection is computed without merging:

    x @ W_eff = x @ W + scale · (x @ A) @ B      (A: (..., d_in, r), B: (..., r, d_out))

The base model's projections take it through ``repro.models.layers.dense``
(or ``x @ w``).  :meth:`LoRAClassifier.merge` builds
``W + scale · A @ B`` once, as the deploy and eval artifact.

The frozen base is ``frozen``: the compiled round chunks take it as an
argument (held once in device memory, never donated) and evaluate
:meth:`LoRAClassifier.bind` of it, so it is never baked into a program as
constants.

Because the FL engines derive *everything* from the trained pytree — the
flat (P, D) round buffer, Eq. 4 aggregation, FLrce's V/A ingest, and the
resource ledger's ``param_count(params)`` byte charges — swapping the model
for its adapter wrapper shrinks uploads/downloads from O(D_full) to
O(rank·(d_in+d_out)) per target matrix with **no engine changes**: the
ledger charges real adapter bytes (regression-tested in
``tests/test_lora.py``), which is exactly how FLrce's communication-
efficiency claims (Eq. 9) extend to the fine-tuning regime.

Adapters are a *param-subset* model (``param_subset = True``): strategies
whose semantics presume the full parameter vector (Dropout's sub-model
masks, TimelyFL's layer freezing) declare ``supports_param_subset = False``
and are rejected by ``run_federated`` (see docs/writing-a-strategy.md).

Two modes:

* default (``exact=False``) — per target matrix, A ~ N(0, 1/d_in) and
  B = 0 are both trained: the merged model starts at the base weights and
  the uploaded delta per matrix is rank·(d_in+d_out) numbers.
* ``exact=True`` — the correctness anchor: rank is forced to
  min(d_in, d_out), the square factor is a *fixed* identity and only the
  other factor trains, so SGD on the adapter reproduces full-matrix SGD
  step for step (with A = I: dL/dB = Aᵀ·dL/dW_eff = dL/dW_eff, hence
  W_eff walks the exact full-training trajectory).  With
  ``train_rest=True`` the non-target leaves (biases, norms) train as
  plain passthrough entries, making a whole FedAvg run on adapters
  equivalent to the same run on the raw model — the merge-equivalence
  test of the adapter-aggregation path.
"""
from __future__ import annotations

import copy
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense

# leaf names treated as low-rank targets: transformer attention/MLP
# projections (wq/wk/wv/wo/wi/wg) and the dense-layer "w" of the paper
# MLP/CNN models.  Embedding/unembedding/norm leaves never match.
DEFAULT_TARGETS = ("wq", "wk", "wv", "wo", "wi", "wg", "w")


@jax.tree_util.register_pytree_node_class
class Factored:
    """A frozen weight ``w`` (..., d_in, d_out) with its low-rank factors:
    ``project(x) = x @ w + scale · (x @ a) @ b``.  A factor that is None
    is the identity (exact mode's square factor).  ``x @ factored`` calls
    ``project``; a consumer that needs the array itself gets the merged
    weight through ``__jax_array__``."""

    def __init__(self, w, a, b, scale: float):
        self.w, self.a, self.b, self.scale = w, a, b, scale

    def tree_flatten(self):
        return (self.w, self.a, self.b), self.scale

    @classmethod
    def tree_unflatten(cls, scale, children):
        return cls(*children, scale)

    @property
    def shape(self):
        return self.w.shape

    @property
    def dtype(self):
        return self.w.dtype

    def project(self, x: jax.Array) -> jax.Array:
        base = dense(x, self.w)
        low = x.astype(jnp.float32)
        if self.a is not None:
            low = low @ self.a
        if self.b is not None:
            low = low @ self.b
        return base + (self.scale * low).astype(base.dtype)

    def __rmatmul__(self, x):
        return self.project(x)

    def merged(self) -> jax.Array:
        a, b = self.a, self.b
        delta = a if b is None else (b if a is None else jnp.matmul(a, b))
        return (self.w.astype(jnp.float32) + self.scale * delta).astype(self.w.dtype)

    def __jax_array__(self):
        return self.merged()


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


class LoRAClassifier:
    """Adapter-only federated training over a frozen base model."""

    param_subset = True

    def __init__(self, base, base_params, rank: int, *, scale: float = 1.0,
                 targets: Sequence[str] = DEFAULT_TARGETS,
                 exact: bool = False, train_rest: bool = False):
        self.base = base
        # arrays (or abstract shapes, for planning a compile)
        self.base_params = jax.tree_util.tree_map(
            lambda l: l if isinstance(l, jax.ShapeDtypeStruct) else jnp.asarray(l), base_params)
        self.rank = int(rank)
        self.scale = float(scale)
        self.targets = tuple(targets)
        self.exact = bool(exact)
        self.train_rest = bool(train_rest)
        self.name = f"lora-{getattr(base, 'name', 'model')}"
        # classify every base leaf once, in flatten order: a 2+D leaf whose
        # final path key names a target gets factors; the rest are frozen
        # (or passthrough-trained under train_rest)
        leaves, self._treedef = jax.tree_util.tree_flatten_with_path(
            self.base_params
        )
        self._plan: List[Tuple[str, str, Tuple[int, ...]]] = []
        for path, leaf in leaves:
            key = _path_str(path)
            last = path[-1].key if hasattr(path[-1], "key") else None
            kind = (
                "target"
                if leaf.ndim >= 2 and last in self.targets
                else "rest"
            )
            self._plan.append((key, kind, tuple(leaf.shape)))
        if not any(kind == "target" for _, kind, _ in self._plan):
            raise ValueError(
                f"no adapter targets matched {self.targets} in "
                f"{getattr(base, 'name', 'model')}'s params"
            )

    # -- adapter geometry ----------------------------------------------------
    def _target_rank(self, d_in: int, d_out: int) -> int:
        return min(d_in, d_out) if self.exact else min(self.rank, d_in, d_out)

    def adapter_dim(self) -> int:
        """Flat dimension of the trained pytree — the D the ledger charges."""
        total = 0
        for _, kind, shape in self._plan:
            if kind == "target":
                *lead, d_in, d_out = shape
                r = self._target_rank(d_in, d_out)
                n_lead = 1
                for l in lead:
                    n_lead *= l
                if self.exact:
                    total += n_lead * r * max(d_in, d_out)
                else:
                    total += n_lead * r * (d_in + d_out)
            elif self.train_rest:
                n = 1
                for l in shape:
                    n *= l
                total += n
        return total

    # -- the ClassifierModel protocol ----------------------------------------
    def init(self, rng: jax.Array) -> Dict:
        adapters: Dict[str, object] = {}
        for (key, kind, shape), (_, leaf) in zip(
            self._plan, jax.tree_util.tree_flatten_with_path(self.base_params)[0]
        ):
            if kind == "target":
                *lead, d_in, d_out = shape
                r = self._target_rank(d_in, d_out)
                if self.exact:
                    # square identity factor is a frozen constant; only the
                    # full-size factor trains (from zero: merged == base)
                    if d_in <= d_out:
                        adapters[key] = {
                            "b": jnp.zeros((*lead, r, d_out), jnp.float32)
                        }
                    else:
                        adapters[key] = {
                            "a": jnp.zeros((*lead, d_in, r), jnp.float32)
                        }
                else:
                    rng, sub = jax.random.split(rng)
                    adapters[key] = {
                        "a": jax.random.normal(
                            sub, (*lead, d_in, r), jnp.float32
                        ) / jnp.sqrt(jnp.float32(d_in)),
                        "b": jnp.zeros((*lead, r, d_out), jnp.float32),
                    }
            elif self.train_rest:
                adapters[key] = leaf
        return adapters

    def factored(self, adapters: Dict) -> object:
        """The base params with every target weight in its :class:`Factored`
        form (and the trained rest leaves in place): the pytree the wrapped
        model evaluates."""
        leaves = jax.tree_util.tree_flatten_with_path(self.base_params)[0]
        out = []
        for (key, kind, _), (_, leaf) in zip(self._plan, leaves):
            if kind == "target":
                ab = adapters[key]
                out.append(Factored(leaf, ab.get("a"), ab.get("b"), self.scale))
            elif self.train_rest:
                out.append(adapters[key])
            else:
                out.append(leaf)
        return jax.tree_util.tree_unflatten(self._treedef, out)

    def merge(self, adapters: Dict) -> object:
        """Base params with every adapter folded in (``W + scale·A@B``): the
        deploy and eval artifact."""
        return jax.tree_util.tree_map(
            lambda l: l.merged() if isinstance(l, Factored) else l,
            self.factored(adapters), is_leaf=lambda l: isinstance(l, Factored))

    @property
    def frozen(self):
        """The frozen base, which the compiled chunks take as an argument."""
        return self.base_params

    def bind(self, frozen) -> "LoRAClassifier":
        """This model over ``frozen`` (the chunk's traced base argument)."""
        view = copy.copy(self)
        view.base_params = frozen
        return view

    @property
    def expert_counts_shape(self):
        return getattr(self.base, "expert_counts_shape", None)

    def loss(self, params, x: jax.Array, y: jax.Array) -> jax.Array:
        return self.base.loss(self.factored(params), x, y)

    def loss_and_counts(self, params, x: jax.Array, y: jax.Array):
        return self.base.loss_and_counts(self.factored(params), x, y)

    def accuracy(self, params, x: jax.Array, y: jax.Array) -> jax.Array:
        return self.base.accuracy(self.factored(params), x, y)

    def flops_per_sample(self) -> float:
        # training still runs fwd+bwd through the full base model; the
        # adapter contraction is a rounding error on top
        return self.base.flops_per_sample()
