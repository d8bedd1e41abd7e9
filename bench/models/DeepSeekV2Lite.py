"""DeepSeek-V2-Lite, cut to its first layers, trained as LoRA adapters on
a frozen bf16 base: its part of the benchmark, found by the config's
``"model": "DeepSeekV2Lite"`` (the module contract is ``PaperCNN.py``'s).

The layer equations follow the published ``config.json`` and the
``modeling_deepseek.py`` beside it:

* MLA without query compression: ``q = h·W_q`` (per head 128 without
  position + 64 under RoPE); ``[c_kv, k_pe] = h·W_kva`` (512 + one shared
  64); ``c_kv`` through RMSNorm; ``[k_nope, v] = c_kv·W_kvb`` (per head
  128 + 128); YaRN RoPE on ``q_pe`` and ``k_pe`` over de-interleaved dims;
  causal softmax at ``192^-0.5 · mscale(40, 0.707)²``; ``o·W_o``;
* layer 0: a SwiGLU MLP of width ``intermediate_size``;
* layers 1+: a float32 softmax gate over the 64 routed experts, greedy
  top-6, not renormalised, times ``routed_scaling_factor``; SwiGLU experts
  of width ``moe_intermediate_size``; the shared experts as one SwiGLU MLP
  of ``n_shared_experts`` times that width, added to every token;
* pre-norm RMSNorm (eps ``rms_norm_eps``) residual blocks, a final norm and
  an untied head over the whole vocabulary.

Departures from the modeling code, in the program and the reference
alike: no auxiliary loss (the router is frozen, so its balance loss has no
gradient to give); the output head is kept on this chip (it would sit on
the deployment's last pipeline stage) so that the loss is the model's;
each sequence's label is the token after it (its domain id), scored at the
last position.

Only the LoRA factors of the four MLA projections train: ``x·W + s·(x·A)·B``
with A ~ N(0, 1/d_in), B = 0, rank and scale from the config.  Token ids
ride in the float32 features.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

TARGETS = ("q_proj", "kv_a_proj", "kv_b_proj", "o_proj")


def num_classes(cfg: Dict) -> int:
    """Labels are text domains; a domain's id is also its sequences' next
    token."""
    return int(cfg["domains"])


def _layers(cfg: Dict) -> Tuple[int, int]:
    """(leading dense layers, MoE layers) of the cut model."""
    n, dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    return dense, n - dense


# -- the program's model ------------------------------------------------------
def program_model(cfg: Dict, frozen):
    from repro.configs.base import ATTN_MLA, ArchConfig, MLAConfig, MoEConfig, YarnScaling
    from repro.models.lm import LMClassifier
    from repro.models.lora import LoRAClassifier

    if float(cfg["rms_norm_eps"]) != 1e-6:
        raise ValueError("the program's RMSNorm takes eps 1e-6")
    rs = cfg["rope_scaling"]
    arch = ArchConfig(
        name=cfg["name"], family="moe", num_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]), num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_key_value_heads"]), d_ff=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]), pattern=(ATTN_MLA,),
        moe=MoEConfig(num_experts=int(cfg["n_routed_experts"]),
                      top_k=int(cfg["num_experts_per_tok"]), aux_loss_weight=0.0,
                      expert_ff=int(cfg["moe_intermediate_size"]),
                      num_shared_experts=int(cfg["n_shared_experts"]),
                      norm_topk_prob=bool(cfg["norm_topk_prob"]),
                      routed_scaling_factor=float(cfg["routed_scaling_factor"]),
                      dropless=True),
        mla=MLAConfig(kv_lora_rank=int(cfg["kv_lora_rank"]),
                      qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
                      qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
                      v_head_dim=int(cfg["v_head_dim"])),
        rope_scaling=YarnScaling(
            factor=float(rs["factor"]),
            original_max_position=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"])),
        first_dense_layers=int(cfg["first_k_dense_replace"]),
        norm="rmsnorm", act="silu", gated_mlp=True,
        rope_theta=float(cfg["rope_theta"]),
        max_position=int(cfg["max_position_embeddings"]),
        dtype="bfloat16", residual_dtype="float32",
    )
    base = LMClassifier(arch, seq_len=int(cfg["seq_len"]), eval_batch=int(cfg["eval_batch"]))
    return LoRAClassifier(base, frozen, rank=int(cfg["lora_rank"]),
                          scale=float(cfg["lora_scale"]), targets=TARGETS)


# -- weights ---------------------------------------------------------------------
def _shapes(cfg: Dict) -> Dict[str, Tuple[int, int]]:
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kv, nope = int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"])
    rope, v = int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"])
    return {"q_proj": (d, h * (nope + rope)), "kv_a_proj": (d, kv + rope),
            "kv_b_proj": (kv, h * (nope + v)), "o_proj": (h * v, d)}


def frozen(cfg: Dict, key: jax.Array) -> Dict:
    """The frozen base in bf16, laid out as the program's ``TransformerLM``:
    normal weights over sqrt(fan-in), 0.02 for the embedding and head, unit
    norm scales."""
    d, vocab = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    e, f = int(cfg["n_routed_experts"]), int(cfg["moe_intermediate_size"])
    fs, fd = int(cfg["n_shared_experts"]) * f, int(cfg["intermediate_size"])
    n_dense, n_moe = _layers(cfg)
    bf = jnp.bfloat16
    keys = iter(jax.random.split(key, 64))

    def w(shape, fan_in, scale=None):
        s = (1.0 / math.sqrt(fan_in)) if scale is None else scale
        return (s * jax.random.normal(next(keys), shape, jnp.float32)).astype(bf)

    def ones(*lead, n):
        return {"scale": jnp.ones((*lead, n), bf)}

    def mixer(*lead):
        out = {name: w((*lead, a, b), a) for name, (a, b) in _shapes(cfg).items()}
        out["kv_norm"] = ones(*lead, n=int(cfg["kv_lora_rank"]))
        return out

    def mlp(*lead, width):
        return {"wi": w((*lead, d, width), d), "wg": w((*lead, d, width), d),
                "wo": w((*lead, width, d), width)}

    dense = [{"norm1": ones(n=d), "mixer": mixer(), "norm2": ones(n=d), "mlp": mlp(width=fd)}
             for _ in range(n_dense)]
    moe = {"router": w((n_moe, d, e), d), "wi": w((n_moe, e, d, f), d),
           "wg": w((n_moe, e, d, f), d), "wo": w((n_moe, e, f, d), f),
           "shared": mlp(n_moe, width=fs)}
    cycle = {"norm1": ones(n_moe, n=d), "mixer": mixer(n_moe), "norm2": ones(n_moe, n=d),
             "mlp": moe}
    return {"embed": w((vocab, d), d, 0.02),
            "decoder": {"cycles": [cycle], "rest": [], "dense": dense},
            "final_norm": ones(n=d), "unembed": w((d, vocab), d, 0.02)}


def _target_keys(cfg: Dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """The adapter keys in the program's flatten order (the frozen tree's
    path to each target leaf), with each leaf's leading dims."""
    n_dense, n_moe = _layers(cfg)
    out = []
    for name in sorted(TARGETS):
        out.append((f"decoder/cycles/0/mixer/{name}", (n_moe,)))
    for i in range(n_dense):
        for name in sorted(TARGETS):
            out.append((f"decoder/dense/{i}/mixer/{name}", ()))
    return out


def init(cfg: Dict, key: jax.Array) -> Dict:
    """The LoRA factors, laid out as the program's ``LoRAClassifier`` lays
    them out: A ~ N(0, 1/d_in), B = 0, float32."""
    r = int(cfg["lora_rank"])
    shapes = _shapes(cfg)
    out = {}
    for i, (path, lead) in enumerate(_target_keys(cfg)):
        a_in, b_out = shapes[path.rsplit("/", 1)[1]]
        out[path] = {
            "a": jax.random.normal(jax.random.fold_in(key, i), (*lead, a_in, r), jnp.float32)
            / jnp.sqrt(jnp.float32(a_in)),
            "b": jnp.zeros((*lead, r, b_out), jnp.float32),
        }
    return out


# -- samples -------------------------------------------------------------------
def sampler(cfg: Dict, mix: Dict, rng: np.random.Generator):
    """Each domain is a Zipf(``zipf_s``) unigram over the whole vocabulary
    under its own permutation, drawn from ``rng``; ``draw(labels)`` draws
    each label's sequence of ``seq_len`` tokens from its domain (float32
    ids)."""
    vocab, n, k = int(cfg["vocab_size"]), int(cfg["seq_len"]), num_classes(cfg)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(mix["zipf_s"]))
    cdf /= cdf[-1]
    perms = np.stack([rng.permutation(vocab) for _ in range(k)]).astype(np.int32)

    def draw(labels):
        u = rng.random((len(labels), n))
        rank = np.minimum(np.searchsorted(cdf, u), vocab - 1)
        return perms[np.asarray(labels)[:, None], rank].astype(np.float32)

    return draw


# -- the plain reference -------------------------------------------------------
def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _yarn(cfg: Dict):
    """YaRN's inverse frequencies and cos/sin factor, as the modeling code's
    ``DeepseekV2YarnRotaryEmbedding`` computes them."""
    rs, dim = cfg["rope_scaling"], int(cfg["qk_rope_head_dim"])
    base, factor = float(cfg["rope_theta"]), float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = inter * (1.0 - mask) + extra * mask

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    return inv, mscale(float(rs["mscale"])) / mscale(float(rs["mscale_all_dim"])), mscale


def _rope(x, pos, cfg: Dict):
    """x (L, ..., d): de-interleave (even dims, then odd), rotate by halves."""
    inv, m, _ = _yarn(cfg)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)      # (L, d/2)
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(cfg: Dict, h, wts, lora, moe: bool, dtype, prec):
    """One decoder layer over one sequence h (L, D)."""
    d, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kvr, nope = int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"])
    rope, vd = int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"])
    eps, s = float(cfg["rms_norm_eps"]), float(cfg["lora_scale"])
    length = h.shape[0]
    up = lambda a: a.astype(dtype)
    dot = lambda a, b: jnp.dot(a, b, precision=prec)

    def proj(x, name):
        w = up(wts["mixer"][name]) + s * dot(lora[name]["a"], lora[name]["b"])
        return dot(x, w)

    x = _rms(h, up(wts["norm1"]["scale"]), eps)
    q = proj(x, "q_proj").reshape(length, nh, nope + rope)
    ckv = proj(x, "kv_a_proj")
    c = _rms(ckv[:, :kvr], up(wts["mixer"]["kv_norm"]["scale"]), eps)
    kv = proj(c, "kv_b_proj").reshape(length, nh, nope + vd)
    pos = jnp.arange(length)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, cfg)], axis=-1)
    k_pe = _rope(ckv[:, kvr:], pos, cfg)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (length, nh, rope))],
                        axis=-1)
    _, _, mscale = _yarn(cfg)
    scale = (nope + rope) ** -0.5 * mscale(float(cfg["rope_scaling"]["mscale_all_dim"])) ** 2
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=prec) * scale
    causal = pos[None, :, None] >= pos[None, None, :]
    p = jax.nn.softmax(jnp.where(causal, scores.astype(jnp.float32), -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p.astype(dtype), kv[..., nope:], precision=prec)
    h = h + proj(o.reshape(length, nh * vd), "o_proj")

    x = _rms(h, up(wts["norm2"]["scale"]), eps)
    mlp = wts["mlp"]

    def swiglu(x, m):
        return dot(jax.nn.silu(dot(x, up(m["wg"]))) * dot(x, up(m["wi"])), up(m["wo"]))

    if not moe:
        return h + swiglu(x, mlp)
    e, top = int(cfg["n_routed_experts"]), int(cfg["num_experts_per_tok"])
    probs = jax.nn.softmax(jnp.dot(x.astype(jnp.float32), mlp["router"].astype(jnp.float32),
                                   precision=lax.Precision.HIGHEST), axis=-1)
    vals, ids = lax.top_k(probs, top)
    if cfg["norm_topk_prob"]:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    vals = vals * float(cfg["routed_scaling_factor"])
    # the dense gate over all experts, zero outside each token's top-k
    gate = jnp.zeros((length, e), jnp.float32).at[pos[:, None], ids].set(vals)
    # each expert on the tokens its gate selects: the (token, expert) pairs
    # in expert order through ragged products (one group an expert), each
    # output weighted by its gate
    pair_e = ids.reshape(-1)
    order = jnp.argsort(pair_e)
    tok = order // top
    sizes = jnp.zeros((e,), jnp.int32).at[pair_e].add(1)

    def rdot(a, w):
        return lax.ragged_dot(a, up(w), sizes, precision=prec, preferred_element_type=dtype)

    xs = x[tok]
    ys = rdot(jax.nn.silu(rdot(xs, mlp["wg"])) * rdot(xs, mlp["wi"]), mlp["wo"])
    routed = jnp.zeros((length, d), dtype).at[tok].add(ys * gate[tok, pair_e[order]][:, None])
    return h + routed + swiglu(x, mlp["shared"])


def _hidden(cfg: Dict, params, frozen, tokens, dtype, prec):
    """Final-normed hidden states (L, D) of one sequence; each layer's bf16
    weights are upcast as it runs (under ``jax.checkpoint``, so a layer's
    intermediates live only while it runs)."""
    n_dense, n_moe = _layers(cfg)
    dec = frozen["decoder"]
    h = frozen["embed"][tokens].astype(dtype)
    for i in range(n_dense):
        lora = {n: params[f"decoder/dense/{i}/mixer/{n}"] for n in TARGETS}
        h = jax.checkpoint(lambda h, w, l: _layer(cfg, h, w, l, False, dtype, prec))(
            h, dec["dense"][i], lora)
    # the MoE layers' weights are stacked (n_moe, ...): scan over them, so
    # that each layer's slice is taken (and upcast) while that layer runs
    lora = {n: params[f"decoder/cycles/0/mixer/{n}"] for n in TARGETS}
    h, _ = lax.scan(lambda h, wl: (jax.checkpoint(
        lambda h, w, l: _layer(cfg, h, w, l, True, dtype, prec))(h, *wl), None),
        h, (dec["cycles"][0], lora))
    return _rms(h, frozen["final_norm"]["scale"].astype(dtype), float(cfg["rms_norm_eps"]))


def _seq_nll(cfg: Dict, params, frozen, x, y, dtype, prec):
    tokens = x.astype(jnp.int32)
    labels = jnp.concatenate([tokens[1:], y[None].astype(jnp.int32)])
    h = _hidden(cfg, params, frozen, tokens, dtype, prec)
    logits = jnp.dot(h, frozen["unembed"].astype(dtype), precision=prec).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def loss(cfg: Dict, params, frozen, x, y, w, dtype, prec):
    """Mean next-token cross-entropy of each sequence (its label after its
    last token), weighted by ``w`` over the batch; one sequence at a time."""
    per = lax.map(lambda xy: jax.checkpoint(
        lambda a, b: _seq_nll(cfg, params, frozen, a, b, dtype, prec))(*xy), (x, y))
    return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)


def correct(cfg: Dict, params, frozen, x, y, dtype, prec):
    """How many sequences' last-position argmax is their label."""
    def one(xy):
        h = _hidden(cfg, params, frozen, xy[0].astype(jnp.int32), dtype, prec)[-1]
        logits = jnp.dot(h, frozen["unembed"].astype(dtype), precision=prec)
        return (jnp.argmax(logits) == xy[1]).astype(jnp.int32)

    return jnp.sum(lax.map(one, (x, y)))


# -- the yardstick's count -------------------------------------------------------
def macs_per_token(cfg: Dict) -> float:
    """Forward multiply-adds a token needs in the cut model: the MLA
    projections, causal attention (scores and values over the mean causal
    context, (L+1)/2 positions), the dense MLP, the router, the top-k routed
    experts and the shared experts, and the full head.  Norms, RoPE,
    softmax and the loss are left out."""
    d, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kvr, nope = int(cfg["kv_lora_rank"]), int(cfg["qk_nope_head_dim"])
    rope, vd = int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"])
    n_dense, n_moe = _layers(cfg)
    ctx = (int(cfg["seq_len"]) + 1) / 2.0
    proj = d * nh * (nope + rope) + d * (kvr + rope) + kvr * nh * (nope + vd) + nh * vd * d
    attend = nh * ((nope + rope) + vd) * ctx
    f = int(cfg["moe_intermediate_size"])
    moe = (d * int(cfg["n_routed_experts"])
           + int(cfg["num_experts_per_tok"]) * 3 * d * f
           + 3 * d * int(cfg["n_shared_experts"]) * f)
    dense = 3 * d * int(cfg["intermediate_size"])
    return ((n_dense + n_moe) * (proj + attend) + n_dense * dense + n_moe * moe
            + d * int(cfg["vocab_size"]))


def flops_per_sample(cfg: Dict) -> float:
    """Model FLOPs to train on one sequence: 2 FLOPs a multiply-add, times
    the forward pass and the backward pass's dX (the base is frozen, so no
    weight gradient; the adapters' own products are under 0.2% and left
    out), over ``seq_len`` tokens.  5.32e12 for DeepSeek-V2-Lite's first 5
    layers at 2,048 tokens."""
    return 2.0 * 2.0 * macs_per_token(cfg) * int(cfg["seq_len"])
