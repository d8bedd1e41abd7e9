"""Tests for the beyond-paper extensions: harmful clients, quantized baseline,
grouped MoE dispatch invariance, mLSTM chunk-size invariance, SWA serve
variant decode."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.configs.base import ArchConfig, MoEConfig
from repro.data import make_federated_classification
from repro.fl import run_federated
from repro.fl.baselines import QuantizedFL
from repro.fl.baselines.quantized import quantize_dequantize
from repro.models import TransformerLM
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.cnn import MLPClassifier


def test_harmful_clients_permute_labels():
    ds_clean = make_federated_classification(num_clients=8, num_samples=800,
                                             num_eval=100, feature_dim=8,
                                             num_classes=4, seed=3)
    ds_bad = make_federated_classification(num_clients=8, num_samples=800,
                                           num_eval=100, feature_dim=8,
                                           num_classes=4, harmful_fraction=0.5,
                                           seed=3)
    diff = sum(
        int((ds_clean.y[ix] != ds_bad.y[ix]).any()) for ix in ds_bad.client_indices
    )
    assert 2 <= diff <= 6  # ~half the clients corrupted
    np.testing.assert_array_equal(ds_clean.eval_y, ds_bad.eval_y)  # eval untouched


def test_quantize_dequantize_error_bound():
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(500,)), jnp.float32)
    dq = quantize_dequantize(u, np.random.default_rng(1), bits=8)
    scale = float(jnp.max(jnp.abs(u))) / 127
    assert float(jnp.max(jnp.abs(dq - u))) <= scale + 1e-6
    # unbiased-ish: mean error small
    assert abs(float(jnp.mean(dq - u))) < scale / 4


def test_quantized_strategy_runs_and_charges_quarter_bytes():
    ds = make_federated_classification(num_clients=6, num_samples=400, num_eval=80,
                                       feature_dim=8, num_classes=3, seed=1)
    model = MLPClassifier(feature_dim=8, num_classes=3, hidden=(12,))
    r = run_federated(model, ds, QuantizedFL(6, 2, 1, seed=0), max_rounds=2,
                      learning_rate=0.1, batch_size=16, seed=0)
    assert r.rounds_run == 2
    # upload = 1/4 of download (8-bit payload vs fp32 model down)
    assert r.ledger.bytes_up == pytest.approx(r.ledger.bytes_down / 4, rel=1e-6)


def _moe_cfg():
    return ArchConfig(
        name="t", family="moe", num_layers=1, d_model=32, num_heads=4,
        num_kv_heads=4, d_ff=64, vocab_size=97, pattern=("attn_global",),
        moe=MoEConfig(num_experts=4, top_k=2, aux_loss_weight=0.0),
    )


@pytest.mark.parametrize("group", [8, 16, 40])
def test_moe_group_size_invariance_dropfree(group):
    """Drop-free routing is per-token, so grouping must not change outputs:
    the capacity path at a capacity of the whole group (E/k) against the
    dropless path."""
    cfg = _moe_cfg()
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 20, 32)), jnp.float32)
    ref, _ = moe_mod.apply_moe(p, x, cfg, capacity_factor=None)
    whole_group = cfg.moe.num_experts / cfg.moe.top_k
    got, _ = moe_mod.apply_moe(p, x, cfg, capacity_factor=whole_group, group_size=group)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_mlstm_chunk_size_invariance(chunk):
    """The chunkwise mLSTM must be exact for any chunk length."""
    cfg = dataclasses.replace(_moe_cfg(), d_ff=0, num_heads=2, num_kv_heads=2,
                              d_model=16, moe=None, family="ssm")
    p = ssm_mod.init_mlstm(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 24, 16)) * 0.5, jnp.float32)
    ref = ssm_mod.apply_mlstm(p, x, cfg, chunk=24)
    got = ssm_mod.apply_mlstm(p, x, cfg, chunk=chunk)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), rtol=2e-3, atol=2e-4)


def test_swa_variant_decode_consistency():
    """The long_500k serve variant (global->windowed) stays self-consistent."""
    from repro.sharding.specs import swa_variant

    cfg = swa_variant(get_arch("deepseek-7b", reduced=True), window=6)
    model = TransformerLM(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    S, B = 14, 2
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    full_logits, _ = model.forward(params, {"tokens": tokens, "labels": tokens})
    cache = model.init_cache(B, S)  # ring-limited to window=6 internally
    errs = []
    for t in range(S):
        lg, cache = model.decode_step(params, tokens[:, t:t+1], cache, jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(
            lg[:, 0].astype(jnp.float32) - full_logits[:, t].astype(jnp.float32)))))
    assert max(errs) < 2e-2, max(errs)
    # and the ring cache really is window-sized
    k_shape = jax.tree_util.tree_leaves(cache)[0].shape
    assert 6 in k_shape
