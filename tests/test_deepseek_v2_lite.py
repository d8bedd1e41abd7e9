"""DeepSeek-V2-Lite's blocks at a tiny size against plain transcriptions.

Tiny config: hidden 64, 4 heads, MLA kv_lora 16 / nope 16 / rope 8 / v 16,
8 routed experts top-2 (not renormalised) and 1 shared, a dense layer 0,
vocabulary 256, seeded random weights:

* MLA with YaRN RoPE against a transcription of DeepSeek-V2's modeling code
  (its YaRN cache, de-interleaved rotate-half, softmax scale);
* the dropless MoE against a per-token loop, including every token routed
  to one expert (nothing drops), and its folding of vmapped tokens into one
  grouped product;
* ``moe_gmm`` against ``kernels/ref.py`` (interpreted), and dX and the
  gates' gradient through ``jax.grad`` against ``lax.ragged_dot``;
* factored LoRA against the merged form;
* a short ``run_federated(engine="sharded", driver="scan")`` job matching
  the sequential engine, with the expert-load counter in ``driver_stats``.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from equivalence import assert_runs_equivalent
from repro.configs import get_arch
from repro.configs.base import ArchConfig, MLAConfig
from repro.kernels import moe_gmm
from repro.kernels.ref import moe_gmm_ref
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.lm import LMClassifier
from repro.models.lora import LoRAClassifier

TARGETS = ("q_proj", "kv_a_proj", "kv_b_proj", "o_proj")


def tiny_cfg(**kw) -> ArchConfig:
    full = get_arch("deepseek-v2-lite")
    cfg = dataclasses.replace(
        full, name="tiny-v2-lite", num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
        d_ff=96, vocab_size=256, dtype="float32", residual_dtype="",
        mla=MLAConfig(kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16),
        moe=dataclasses.replace(full.moe, num_experts=8, top_k=2, expert_ff=32,
                                num_shared_experts=1),
    )
    return dataclasses.replace(cfg, **kw)


def _normal(rng, *shape, scale=1.0):
    return jnp.asarray(scale * rng.normal(size=shape), jnp.float32)


# -- MLA + YaRN ------------------------------------------------------------------
def _yarn_cos_sin(cfg, seq_len):
    """DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache, in numpy."""
    ys, dim, base = cfg.rope_scaling, cfg.mla.qk_rope_head_dim, cfg.rope_theta

    def corr_dim(rot):
        return (dim * math.log(ys.original_max_position / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(ys.beta_fast)), 0)
    high = min(math.ceil(corr_dim(ys.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    freq_inter = 1.0 / (ys.factor * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    freqs = np.outer(np.arange(seq_len, dtype=np.float32), inv_freq)

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    m = get_mscale(ys.factor, ys.mscale) / get_mscale(ys.factor, ys.mscale_all_dim)
    emb = np.concatenate([freqs, freqs], axis=-1)
    return np.cos(emb) * m, np.sin(emb) * m


def _mla_transcription(p, x, cfg):
    """DeepseekV2Attention.forward (no q_lora), one batch row at a time."""
    m, nh = cfg.mla, cfg.num_heads
    b, s, _ = x.shape
    x, p = np.asarray(x, np.float64), jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    cos, sin = _yarn_cos_sin(cfg, s)

    def rotate_half(t):
        h = t.shape[-1] // 2
        return np.concatenate([-t[..., h:], t[..., :h]], axis=-1)

    def rope(t):                                     # (s, heads, d): view(d/2, 2).T
        t = t.reshape(*t.shape[:-1], t.shape[-1] // 2, 2).swapaxes(-1, -2).reshape(t.shape)
        return t * cos[:, None, :] + rotate_half(t) * sin[:, None, :]

    def ys_mscale(scale, mm):
        return 1.0 if scale <= 1 else 0.1 * mm * math.log(scale) + 1.0

    scale = m.qk_head_dim ** -0.5 * ys_mscale(cfg.rope_scaling.factor,
                                               cfg.rope_scaling.mscale_all_dim) ** 2
    out = []
    for r in range(b):
        h = x[r]
        q = (h @ p["q_proj"]).reshape(s, nh, m.qk_head_dim)
        q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
        ckv = h @ p["kv_a_proj"]
        c, k_pe = ckv[:, : m.kv_lora_rank], ckv[:, m.kv_lora_rank:]
        c = c / np.sqrt(np.mean(c * c, -1, keepdims=True) + 1e-6) * p["kv_norm"]["scale"]
        kv = (c @ p["kv_b_proj"]).reshape(s, nh, m.qk_nope_head_dim + m.v_head_dim)
        k_nope, v = kv[..., : m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]
        q_pe, k_pe = rope(q_pe), rope(k_pe[:, None, :])
        qs = np.concatenate([q_nope, q_pe], -1)
        ks = np.concatenate([k_nope, np.broadcast_to(k_pe, (s, nh, m.qk_rope_head_dim))], -1)
        w = np.einsum("qhd,khd->hqk", qs, ks) * scale
        w = np.where(np.tril(np.ones((s, s), bool))[None], w, -np.inf)
        w = np.exp(w - w.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        o = np.einsum("hqk,khd->qhd", w, v).reshape(s, nh * m.v_head_dim)
        out.append(o @ p["o_proj"])
    return np.stack(out)


def test_mla_with_yarn_matches_transcription():
    cfg = tiny_cfg()
    p = attn.init_mla(jax.random.PRNGKey(0), cfg, jnp.float32)
    p["kv_norm"]["scale"] = _normal(np.random.default_rng(1), 16, scale=0.3) + 1.0
    x = _normal(np.random.default_rng(2), 2, 24, 64)
    pos = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))
    got = jax.jit(lambda p, x: attn.mla_block(p, x, pos, cfg, kv_chunk=8))(p, x)   # 3 KV chunks
    np.testing.assert_allclose(np.asarray(got), _mla_transcription(p, x, cfg),
                               rtol=2e-4, atol=2e-5)
    assert attn.mla_softmax_scale(get_arch("deepseek-v2-lite")) == pytest.approx(0.11472, abs=1e-5)


# -- dropless MoE ------------------------------------------------------------------
def _moe_per_token(p, x, cfg):
    """Per token: float32 softmax gate, greedy top-k, no renormalisation,
    the routed SwiGLU experts and the shared MLP."""
    moe = cfg.moe
    xt = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    pp = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    silu = lambda z: z / (1 + np.exp(-z))
    out = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        logits = xt[t] @ pp["router"]
        prob = np.exp(logits - logits.max())
        prob /= prob.sum()
        for e in np.argsort(-prob, kind="stable")[: moe.top_k]:
            h = silu(xt[t] @ pp["wg"][e]) * (xt[t] @ pp["wi"][e])
            out[t] += moe.routed_scaling_factor * prob[e] * (h @ pp["wo"][e])
        sh = pp["shared"]
        out[t] += (silu(xt[t] @ sh["wg"]) * (xt[t] @ sh["wi"])) @ sh["wo"]
    return out.reshape(x.shape)


@pytest.mark.parametrize("routing", ["random", "all_to_expert_3"])
def test_dropless_moe_matches_per_token_loop(routing):
    cfg = tiny_cfg()
    p = moe_mod.init_moe(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = _normal(np.random.default_rng(4), 2, 12, 64)
    if routing != "random":
        # every token's first choice is expert 3: 24 tokens on one expert,
        # 6x a capacity of 1.25 x the mean load
        x = jnp.abs(x)
        p["router"] = p["router"].at[:, 3].set(1.0)
    out, aux, counts = jax.jit(lambda p, x: moe_mod.apply_moe_dropless(p, x, cfg))(p, x)
    np.testing.assert_allclose(np.asarray(out), _moe_per_token(p, x, cfg), rtol=2e-4, atol=2e-5)
    assert float(jnp.sum(counts)) == 2 * 12 * cfg.moe.top_k
    if routing != "random":
        assert float(counts[3]) == 24


def _routed_ragged(x, ids, gates, w_gate, w_up, w_down, act="silu"):
    """``moe.routed_experts`` in plain JAX: ``lax.ragged_dot`` over the
    sorted pairs, differentiated by JAX itself."""
    n, k = ids.shape
    flat = ids.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((w_up.shape[0],), jnp.int32).at[flat].add(1)
    xs = x[order // k]
    rd = lambda a, w: jax.lax.ragged_dot(a, w, sizes, precision="highest")
    y = rd(jax.nn.silu(rd(xs, w_gate)) * rd(xs, w_up), w_down)
    return jnp.zeros_like(x).at[order // k].add(y * gates.reshape(-1)[order][:, None])


def test_moe_gmm_matches_reference_and_grads():
    rng = np.random.default_rng(5)
    for m, k, n, e, transpose in [(64, 32, 48, 4, False), (40, 16, 24, 8, True)]:
        sizes = rng.multinomial(m, np.ones(e) / e).astype(np.int32)
        sizes[0] += sizes[1]
        sizes[1] = 0                                            # an empty expert
        pad = moe_gmm.padded_rows(m) - m
        sizes[-1] += pad
        lhs = _normal(rng, m + pad, k)
        rhs = _normal(rng, e, *((n, k) if transpose else (k, n)))
        got = moe_gmm.gmm(lhs, rhs, jnp.asarray(sizes), transpose_rhs=transpose)
        want = moe_gmm_ref(lhs, rhs, jnp.asarray(sizes), transpose_rhs=transpose)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    # dX and the gates' gradient of the sorted grouped products
    cfg = tiny_cfg()
    p = moe_mod.init_moe(jax.random.PRNGKey(6), cfg, jnp.float32)
    x = _normal(rng, 20, 64)
    gates, ids, _ = moe_mod.route(p["router"], x, cfg.moe)

    def via_ragged(x, gates):
        return _routed_ragged(x, ids, gates, p["wg"], p["wi"], p["wo"])

    def kernel(x, gates):
        return moe_mod.routed_experts(x, ids, gates, p["wg"], p["wi"], p["wo"])

    ct = _normal(rng, 20, 64)
    loss = lambda f: lambda a, b: jnp.sum(f(a, b) * ct)
    got = jax.jit(jax.grad(loss(kernel), argnums=(0, 1)))(x, gates)
    want = jax.jit(jax.grad(loss(via_ragged), argnums=(0, 1)))(x, gates)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


def _primitives(jaxpr):
    """The names of the primitives in a jaxpr and its sub-jaxprs."""
    out = set()
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out |= _primitives(sub)
    return out


def test_trained_experts_get_their_gradient(monkeypatch):
    """Full-parameter training of a dropless config: every gradient through
    ``TransformerLM.loss``, the experts' included, and under a per-example
    map of shared weights, equals JAX's own autodiff of ``lax.ragged_dot``;
    per-client expert weights under a map raise; frozen experts (LoRA)
    compute no weight gradient."""
    from repro.models.transformer import TransformerLM

    cfg = tiny_cfg(num_layers=2)                           # dense layer 0, MoE layer 1
    model = TransformerLM(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(11))
    rng = np.random.default_rng(12)
    tokens = jnp.asarray(rng.integers(0, 256, (2, 12)), jnp.int32)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    got = jax.jit(jax.grad(model.loss))(params, batch)
    monkeypatch.setattr(moe_mod, "routed_experts", _routed_ragged)
    want = jax.jit(jax.grad(model.loss))(params, batch)
    monkeypatch.undo()
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))
    experts = got["decoder"]["cycles"][0]["mlp"]              # (1 cycle, E, ...)
    for w in ("wi", "wg", "wo"):
        assert experts[w].shape[1] == cfg.moe.num_experts
        assert float(jnp.min(jnp.max(jnp.abs(experts[w][0]), axis=(1, 2)))) > 0, w

    # shared expert weights under a per-example map (the sequential engine)
    mp = moe_mod.init_moe(jax.random.PRNGKey(13), cfg, jnp.float32)
    xs = _normal(rng, 3, 1, 5, 64)
    mapped = lambda p, f: jnp.sum(jax.vmap(lambda xe: f(p, xe)[0])(xs) ** 2)
    dropless = lambda p, xe: moe_mod.apply_moe_dropless(p, xe, cfg)
    got = jax.jit(jax.grad(lambda p: mapped(p, dropless)))(mp)
    monkeypatch.setattr(moe_mod, "routed_experts", _routed_ragged)
    want = jax.jit(jax.grad(lambda p: sum(jnp.sum(dropless(p, xe)[0] ** 2) for xe in xs)))(mp)
    monkeypatch.undo()
    for w in ("wi", "wg", "wo", "router"):
        np.testing.assert_allclose(np.asarray(got[w]), np.asarray(want[w]), rtol=2e-4, atol=1e-5)
    with pytest.raises(NotImplementedError):                # per-client expert weights
        jax.vmap(jax.grad(lambda p: mapped(p, dropless)))(
            jax.tree_util.tree_map(lambda a: jnp.stack([a, a]), mp))

    _, _, lora, ada = _tiny_lora(num_layers=2)
    x = jnp.asarray(rng.integers(0, 256, (2, 12)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 256, 2), jnp.int32)
    prims = _primitives(jax.make_jaxpr(jax.grad(lora.loss))(ada, x, y).jaxpr)
    assert "pallas_call" in prims and not any("ragged_dot" in p for p in prims)


def _kernel_rows(jaxpr):
    """The lhs rows of every ``pallas_call`` in a jaxpr and its sub-jaxprs."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.invars[4].aval.shape[0])        # after 4 scalar prefetches
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out.extend(_kernel_rows(sub))
    return out


def test_vmapped_tokens_share_one_grouped_product():
    """Under the cohort's client vmap and the per-example vmap, every
    mapped token joins one grouped product per expert matrix."""
    cfg = tiny_cfg()
    p = moe_mod.init_moe(jax.random.PRNGKey(7), cfg, jnp.float32)
    x = _normal(np.random.default_rng(8), 3, 2, 1, 10, 64)     # (P, B, 1, S, D)

    def example_loss(xe):
        return jnp.sum(moe_mod.apply_moe_dropless(p, xe, cfg)[0] ** 2)

    def client(xc):
        return jax.grad(lambda z: jnp.sum(jax.vmap(example_loss)(z)))(xc)

    rows = _kernel_rows(jax.make_jaxpr(jax.vmap(client))(x).jaxpr)
    # forward: gate, up, down; backward: gate, up recomputed, then 3 dX
    assert rows == [moe_gmm.padded_rows(3 * 2 * 10 * cfg.moe.top_k)] * 8
    g = jax.jit(jax.vmap(client))(x)
    one = jax.jit(jax.grad(example_loss))
    for c, b in ((0, 0), (2, 1)):
        np.testing.assert_allclose(np.asarray(g[c, b]), np.asarray(one(x[c, b])),
                                   rtol=1e-4, atol=1e-5)


# -- factored LoRA -----------------------------------------------------------------
def _tiny_lora(seed=0, **kw):
    cfg = tiny_cfg(**kw)
    base = LMClassifier(cfg, seq_len=12, remat=False)
    params = base.init(jax.random.PRNGKey(seed))
    lora = LoRAClassifier(base, params, rank=4, scale=2.0, targets=TARGETS)
    ada = lora.init(jax.random.PRNGKey(seed + 1))
    # B = 0 at init: give B values so that the adapters move the output
    ada = jax.tree_util.tree_map(lambda a: a + 0.1, ada)
    return cfg, base, lora, ada


def test_factored_lora_matches_merged():
    cfg, base, lora, ada = _tiny_lora(num_layers=1)        # layer 0: MLA + dense MLP
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.integers(0, 256, (3, 12)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 256, 3), jnp.int32)
    got, g_got = jax.jit(jax.value_and_grad(lora.loss))(ada, x, y)
    merged = lambda a: base.loss(lora.merge(a), x, y)
    want, g_want = jax.jit(jax.value_and_grad(merged))(ada)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_got), jax.tree_util.tree_leaves(g_want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-6)
    assert lora.adapter_dim() == 4 * sum(a + b for a, b in (
        (64, 4 * 24), (64, 16 + 8), (16, 4 * 32), (4 * 16, 64)))


def test_param_counts_of_the_cut():
    full = get_arch("deepseek-v2-lite")
    assert full.param_count() == pytest.approx(15.7e9, rel=0.01)
    assert dataclasses.replace(full, num_layers=5).param_count() == 2_839_831_040
    lora = LoRAClassifier(LMClassifier(dataclasses.replace(full, num_layers=5), seq_len=2048),
                          jax.eval_shape(LMClassifier(dataclasses.replace(full, num_layers=5),
                                                      seq_len=2048).init,
                                         jax.random.PRNGKey(0)),
                          rank=16, scale=2.0, targets=TARGETS)
    assert lora.adapter_dim() == 5 * 263_168


# -- the federated path ------------------------------------------------------------
def test_sharded_scan_job_matches_sequential_engine():
    from repro.data.synthetic import FederatedDataset
    from repro.fl import FLrce, run_federated
    from repro.fl.stats_schema import validate_driver_stats
    from repro.launch import make_engine_mesh

    cfg, base, lora, ada = _tiny_lora(seed=2, num_layers=2)
    rng = np.random.default_rng(10)
    n = 16
    x = rng.integers(0, 256, (n, 12)).astype(np.float32)
    y = rng.integers(0, 256, n).astype(np.int32)
    ds = FederatedDataset(x=x, y=y, client_indices=np.array_split(np.arange(n), 4),
                          eval_x=x[:4], eval_y=y[:4], num_classes=256)
    kw = dict(max_rounds=2, learning_rate=0.05, batch_size=2, seed=0, init_params=ada)
    mk = lambda: FLrce(num_clients=4, clients_per_round=2, local_epochs=1,
                       dim=lora.adapter_dim(), use_early_stopping=False, seed=0)
    seq = run_federated(lora, ds, mk(), engine="sequential", **kw)
    scan = run_federated(lora, ds, mk(), engine="sharded", mesh=make_engine_mesh(1, 1),
                         driver="scan", scan_chunk_rounds=2, **kw)
    assert_runs_equivalent(seq, scan, bitwise=False, params_atol=1e-5)
    validate_driver_stats(scan.driver_stats)
    assert scan.driver_stats["expert_load_max_over_mean"] >= 1.0
    assert "expert_load_max_over_mean" not in seq.driver_stats
