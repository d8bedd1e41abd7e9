"""Mixture-of-Experts FFN with top-k routing: capacity-based dispatch, or
dropless grouped expert products.

Dispatch follows the Switch/Mesh-TF formulation: tokens are routed to experts
through dense one-hot dispatch/combine tensors, which (a) keeps everything
statically shaped for pjit, and (b) lowers to the expert-parallel all-to-all
pattern when the expert weights are sharded.  Capacity factor bounds the
per-expert token buffer; overflowing tokens are dropped (residual passes
through), exactly as in production MoE trainers.

A Switch-style load-balance auxiliary loss is returned alongside the output.

The dropless path (:func:`apply_moe_dropless`; see :func:`is_dropless`)
drops no token at any load: the (token, expert) pairs are sorted by expert
and each expert's SwiGLU runs as grouped products over the experts' token
counts (``repro.kernels.moe_gmm``), then the pairs are un-permuted and
combined with their gates.  Shared experts (DeepSeek) are one MLP added to
every token.  The backward pass computes dX through the transposed grouped
products and the gates' gradient; a gradient of the expert weights only
where they train (through ``lax.ragged_dot``'s transpose), never for frozen
experts (a LoRA run).  Under ``vmap`` (the cohort's clients and their
examples) the mapped tokens join one grouped product per layer.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.kernels import fold_vmap, moe_gmm
from repro.kernels.ref import moe_gmm_ref
from repro.models.layers import activation, apply_mlp, dense_init, init_mlp


def init_moe(rng, cfg: ArchConfig, dtype) -> Dict:
    assert cfg.moe is not None
    d, e = cfg.d_model, cfg.moe.num_experts
    f = cfg.moe.expert_width(cfg.d_ff)
    rr, ri, rg, ro = jax.random.split(rng, 4)

    def experts(key, fan_in, fan_out):
        return jax.vmap(lambda i: dense_init(jax.random.fold_in(key, i), fan_in, fan_out, dtype))(
            jnp.arange(e))

    params = {
        "router": dense_init(rr, d, e, jnp.float32),  # router always fp32
        "wi": experts(ri, d, f),
        "wo": experts(ro, f, d),
    }
    if cfg.gated_mlp:
        params["wg"] = experts(rg, d, f)
    if cfg.moe.num_shared_experts:
        params["shared"] = init_mlp(jax.random.fold_in(rr, 1), d,
                                    cfg.moe.num_shared_experts * f, cfg.gated_mlp, dtype)
    return params


def is_dropless(moe, capacity_factor: float | None) -> bool:
    """Whether a MoE layer runs the dropless path: a config that asks for it
    on every pass, or a call with no capacity (decode)."""
    return moe.dropless or capacity_factor is None


def _pin(x: jax.Array, spec_dims) -> jax.Array:
    from jax.sharding import PartitionSpec as P_

    return jax.lax.with_sharding_constraint(x, P_(*spec_dims))


def apply_moe(
    params: Dict,
    x: jax.Array,              # (B, S, D)
    cfg: ArchConfig,
    *,
    capacity_factor: float | None = 1.25,
    group_size: int | None = None,
    batch_axes=None,
    expert_axis=None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux load-balance loss scalar).

    ``capacity_factor=None`` (the decode path, where a dropped token would
    corrupt generation) and ``MoEConfig.dropless`` run
    :func:`apply_moe_dropless`, which drops no token.

    ``group_size`` (beyond-paper §Perf optimization): dispatch within groups
    of G tokens instead of over all N.  The dense one-hot dispatch einsum
    costs 2·N·G·cf·k·D flops (quadratic in the dispatch granularity) — at
    N = 65 536 ungrouped dispatch is ~30x the expert FFN compute, at
    G = 2 048 it is a few percent.  Capacity is enforced per group, exactly
    the Switch/Mesh-TF formulation."""
    moe = cfg.moe
    if is_dropless(moe, capacity_factor):
        out, aux, _ = apply_moe_dropless(params, x, cfg)
        return out, aux
    b, s, d = x.shape
    e, k = moe.num_experts, moe.top_k
    n = b * s
    xt = x.reshape(n, d)

    logits = xt.astype(jnp.float32) @ params["router"]          # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)             # (N, k)
    # renormalize the chosen gates (mixtral-style)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    g = n if not group_size else min(group_size, n)
    pad = (-n) % g
    n_pad = n + pad
    ng = n_pad // g
    capacity = max(1, int(capacity_factor * g * k / e))

    onehot = jax.nn.one_hot(expert_ids, e, dtype=jnp.int32)     # (N, k, E)
    if pad:
        onehot = jnp.pad(onehot, ((0, pad), (0, 0), (0, 0)))
        gate_pad = jnp.pad(gate_vals, ((0, pad), (0, 0)))
        x_pad = jnp.pad(xt, ((0, pad), (0, 0)))
    else:
        gate_pad, x_pad = gate_vals, xt
    onehot_g = onehot.reshape(ng, g, k, e)
    gates_g = gate_pad.reshape(ng, g, k)
    x_g = x_pad.reshape(ng, g, d)

    # position of each (token, choice) within its expert's per-group buffer
    flat_oh = onehot_g.reshape(ng, g * k, e)
    pos = jnp.cumsum(flat_oh, axis=1) * flat_oh                  # 1-based
    pos = (pos - 1).reshape(ng, g, k, e)
    within = (pos >= 0) & (pos < capacity)

    slot_oh = jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1), capacity, dtype=x.dtype)
    keep = onehot_g.astype(x.dtype) * within.astype(x.dtype)
    dispatch = jnp.einsum("Gnke,Gnkec->Gnec", keep, slot_oh)
    combine = jnp.einsum("Gnk,Gnke,Gnkec->Gnec", gates_g.astype(x.dtype), keep, slot_oh)

    # pin the group dim to the data axes: groups are disjoint token sets, so
    # a data-sharded G makes the dispatch/combine einsums fully LOCAL — without
    # this GSPMD contracts the sharded token dim into partial-sum all-reduces
    # of the (G,E,C,D) buffers (~4.3 TB/device/step at dbrx scale).
    if batch_axes is not None and ng % 2 == 0:
        dispatch = _pin(dispatch, (batch_axes, None, expert_axis, None))
        combine = _pin(combine, (batch_axes, None, expert_axis, None))

    expert_in = jnp.einsum("Gnec,Gnd->Gecd", dispatch, x_g)      # (NG, E, C, D)
    if batch_axes is not None and ng % 2 == 0:
        expert_in = _pin(expert_in, (batch_axes, expert_axis, None, None))

    # expert FFN with the group dim kept explicit as a batch dim — a
    # transpose+reshape here loses the G sharding through GSPMD and
    # re-materializes the (G,E,C,D) buffers with all-reduces
    wg = params.get("wg")
    h = jnp.einsum("Gecd,edf->Gecf", expert_in, params["wi"])
    if wg is not None:
        h = activation(cfg.act, jnp.einsum("Gecd,edf->Gecf", expert_in, wg)) * h
    else:
        h = activation(cfg.act, h)
    expert_out = jnp.einsum("Gecf,efd->Gecd", h, params["wo"])   # (NG, E, C, D)
    if batch_axes is not None and ng % 2 == 0:
        expert_out = _pin(expert_out, (batch_axes, expert_axis, None, None))
    out = jnp.einsum("Gnec,Gecd->Gnd", combine, expert_out).reshape(n_pad, d)[:n]

    # Switch aux loss: E * sum_e (fraction of tokens to e) * (mean router prob e)
    token_frac = jnp.mean(onehot.astype(jnp.float32)[:n].sum(1), axis=0)  # (E,)
    prob_frac = jnp.mean(probs, axis=0)                          # (E,)
    aux = e * jnp.sum(token_frac * prob_frac) * moe.aux_loss_weight

    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# dropless: sorted (token, expert) pairs through grouped expert products
# ---------------------------------------------------------------------------
def route(router: jax.Array, xt: jax.Array, moe) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Gates (N, k) and expert ids (N, k) of tokens ``xt`` (N, D), and the
    softmax probabilities (N, E): float32 logits at ``HIGHEST`` (DeepSeek
    computes its gate in float32), greedy top-k, renormalised or not, times
    the routed scale."""
    logits = jnp.dot(xt.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = lax.top_k(probs, moe.top_k)
    if moe.norm_topk_prob:
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates * moe.routed_scaling_factor, ids, probs


def _dispatch(x, ids, num_experts):
    """Sort the (token, choice) pairs by expert: the sort order, each sorted
    pair's token, the experts' counts (the last one holding the pad rows)
    and the sorted tokens, zero-padded to a whole number of m tiles."""
    n, k = ids.shape
    m = n * k
    m_pad = moe_gmm.padded_rows(m)
    flat = ids.reshape(m)
    order = jnp.argsort(flat, stable=True)
    token = order // k
    counts = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    group_sizes = counts.at[num_experts - 1].add(m_pad - m)
    xs = jnp.pad(x[token], ((0, m_pad - m), (0, 0)))
    return order, token, group_sizes, xs


def _expert_inputs(x, ids, w_gate, w_up, act, product=moe_gmm.gmm):
    """The sorted pairs' gate and up products, and their SwiGLU."""
    dt = w_up.dtype
    with jax.named_scope("moe/dispatch"):
        order, token, group_sizes, xs = _dispatch(x.astype(dt), ids, w_up.shape[0])
    with jax.named_scope("moe/experts"):
        g = product(xs, w_gate, group_sizes)
        u = product(xs, w_up, group_sizes)
        h = activation(act, g) * u
    return order, token, group_sizes, g, u, h


def _experts_fwd_impl(x, ids, gates, w_gate, w_up, w_down, act, product=moe_gmm.gmm):
    n, k = ids.shape
    m = n * k
    order, _, group_sizes, _, _, h = _expert_inputs(x, ids, w_gate, w_up, act, product)
    with jax.named_scope("moe/experts"):
        y = product(h.astype(w_down.dtype), w_down, group_sizes)[:m]   # (m, D)
    with jax.named_scope("moe/combine"):
        gs = gates.reshape(m)[order].astype(jnp.float32)
        out = jnp.sum((y * gs[:, None])[jnp.argsort(order)].reshape(n, k, -1), axis=1)
    return out.astype(x.dtype)


def _experts_bwd_impl(x, ids, gates, w_gate, w_up, w_down, d_out, act):
    """dX and the gates' cotangent.  The sorted pairs' gate and up products
    are recomputed (the forward keeps only its inputs), and
    ``d_gate = <dy, h·W_down> = <dy·W_downᵀ, h>`` needs no down product."""
    n, k = ids.shape
    m = n * k
    dt = w_up.dtype
    order, token, group_sizes, g, u, h = _expert_inputs(x, ids, w_gate, w_up, act)
    inv = jnp.argsort(order)
    with jax.named_scope("moe/combine"):
        dy = jnp.pad(d_out.astype(dt)[token], ((0, g.shape[0] - m), (0, 0)))
        gs = jnp.pad(gates.reshape(m)[order].astype(jnp.float32), (0, g.shape[0] - m))
    with jax.named_scope("moe/experts"):
        dh0 = moe_gmm.gmm(dy, w_down, group_sizes, transpose_rhs=True)     # dy·W_downᵀ
        d_gates = jnp.sum(dh0 * h, axis=-1)[:m][inv]
        dg, du = jax.vjp(lambda a, b: activation(act, a) * b, g, u)[1](dh0 * gs[:, None])
        dxs = (moe_gmm.gmm(dg.astype(dt), w_gate, group_sizes, transpose_rhs=True)
               + moe_gmm.gmm(du.astype(dt), w_up, group_sizes, transpose_rhs=True))
    with jax.named_scope("moe/dispatch"):
        dx = jnp.sum(dxs[:m][inv].reshape(n, k, -1), axis=1)
    return dx.astype(x.dtype), d_gates.reshape(n, k).astype(gates.dtype)


def _weight_grads(act: str):
    """The expert weights' cotangents ``(x, ids, gates, d_out, w_gate, w_up,
    w_down) -> (dW_gate, dW_up, dW_down)``, through ``lax.ragged_dot``'s
    transpose.  A mapped call (each instance its own cotangent of shared
    weights) runs its instances one at a time."""
    def impl(x, ids, gates, d_out, w_gate, w_up, w_down):
        fwd = lambda *w: _experts_fwd_impl(x, ids, gates, *w, act, product=moe_gmm_ref)
        return jax.vjp(fwd, w_gate, w_up, w_down)[1](d_out)

    fn = jax.custom_batching.custom_vmap(impl)

    @fn.def_vmap
    def rule(axis_size, in_batched, *args):
        if any(in_batched[4:]):
            raise NotImplementedError("per-client expert weights under vmap")
        tokens = [a if bat else jnp.broadcast_to(a, (axis_size, *a.shape))
                  for a, bat in zip(args[:4], in_batched[:4])]
        return lax.map(lambda t: fn(*t, *args[4:]), tuple(tokens)), (True, True, True)

    return fn


_EXPERTS = {}


def routed_experts(x, ids, gates, w_gate, w_up, w_down, act: str = "silu"):
    """Σ_j gates[t, j] · SwiGLU_{ids[t, j]}(x[t]) over every (token, choice)
    pair, as sorted grouped products: no capacity, no dropped token.  The
    backward pass computes the expert weights' cotangent only where they
    are differentiated (``symbolic_zeros``): frozen experts cost none."""
    if act not in _EXPERTS:
        fwd = fold_vmap(lambda x, i, g, wg, wu, wd: _experts_fwd_impl(x, i, g, wg, wu, wd, act), 3)
        bwd = fold_vmap(
            lambda x, i, g, d, wg, wu, wd: _experts_bwd_impl(x, i, g, wg, wu, wd, d, act), 4)
        weight_grads = _weight_grads(act)

        @jax.custom_vjp
        def experts(x, ids, gates, w_gate, w_up, w_down):
            return fwd(x, ids, gates, w_gate, w_up, w_down)

        def experts_fwd(*primals):
            args = tuple(p.value for p in primals)
            # whether the experts train rides in the residuals' structure
            trained = () if any(p.perturbed for p in primals[3:]) else None
            return fwd(*args), (args, trained)

        def experts_bwd(saved, d_out):
            (x, ids, gates, w_gate, w_up, w_down), trained = saved
            if isinstance(d_out, jax.custom_derivatives.SymbolicZero):
                return (None,) * 6
            dx, d_gates = bwd(x, ids, gates, d_out, w_gate, w_up, w_down)
            dw = (None,) * 3
            if trained is not None:
                dw = weight_grads(x, ids, gates, d_out, w_gate, w_up, w_down)
            return (dx, None, d_gates, *dw)

        experts.defvjp(experts_fwd, experts_bwd, symbolic_zeros=True)
        _EXPERTS[act] = experts
    return _EXPERTS[act](x, ids, gates, w_gate, w_up, w_down)


def apply_moe_dropless(params: Dict, x: jax.Array, cfg: ArchConfig):
    """Dropless MoE over (B, S, D): returns the output, the Switch aux loss
    (times ``aux_loss_weight``) and the per-expert count of routed
    assignments (E,) float32."""
    moe = cfg.moe
    if "wg" not in params:
        raise NotImplementedError("the dropless MoE path runs gated (SwiGLU) experts")
    b, s, d = x.shape
    e = moe.num_experts
    xt = x.reshape(b * s, d)
    with jax.named_scope("moe/route"):
        gates, ids, probs = route(params["router"], xt, moe)
        counts = jnp.zeros((e,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    out = routed_experts(xt, ids, gates, params["wg"], params["wi"], params["wo"], cfg.act)
    if "shared" in params:
        with jax.named_scope("moe/shared"):
            out = out + apply_mlp(params["shared"], xt, cfg.act)
    aux = jnp.zeros((), jnp.float32)
    if moe.aux_loss_weight:
        token_frac = counts / (b * s)
        aux = e * jnp.sum(token_frac * jnp.mean(probs, axis=0)) * moe.aux_loss_weight
    return out.reshape(b, s, d), aux, counts
