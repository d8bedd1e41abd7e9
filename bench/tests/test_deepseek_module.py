"""DeepSeek-V2-Lite's model module at a tiny size: its config written to a
temporary directory and run through ``run.run_cell`` on the CPU.  A sound
run of the program (MLA, a dense layer 0, dropless MoE with a shared
expert, LoRA on a frozen bf16 base) agrees with the module's plain float32
reference; with half of every batch left out it does not."""
import json
import os
import shutil

import pytest

import run
from test_correct import SEED, cell

CONFIG = {
    "name": "tiny_deepseek", "model": "DeepSeekV2Lite", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "norm_topk_prob": False, "routed_scaling_factor": 1.0,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "max_position_embeddings": 163840,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "vocab_size": 256, "seq_len": 16, "domains": 8, "lora_rank": 4, "lora_scale": 2.0,
    "eval_batch": 4,
}
MIX = {"clients": 6, "clients_per_round": 2, "local_epochs": 1, "batch_size": 2,
       "learning_rate": 0.05, "partition": "label", "alpha": 0.5, "num_samples": 40,
       "num_eval": 8, "zipf_s": 1.1, "partition_seed": 0, "selection_seed": 0,
       "explore_decay": 0.98, "es_threshold": 1.0, "chunk_rounds": 2,
       "chunk_seconds": 1e9, "min_window_chunks": 1, "trace_chunks": 1, "check_rounds": 2}
# bf16 matmul operands in the program against the float32 reference
LIMITS = {"selection": 0, "es": 0, "loss": 0.01, "update": 0.05, "model": 0.05,
          "omega": 0.05, "conflicts": 0}


def ds_cell(tmp_path):
    shutil.copy(os.path.join(run.HERE, "models", "DeepSeekV2Lite.py"), tmp_path)
    (tmp_path / "tiny_deepseek.json").write_text(json.dumps(CONFIG))
    c = cell()
    c.cell = {"name": "tiny_deepseek.cell", "chips": 1}
    c.cfg = json.loads((tmp_path / "tiny_deepseek.json").read_text())
    c.model = run.load_model(c.cfg["model"], str(tmp_path))
    c.mix, c.limits = MIX, LIMITS
    return c


def half_batches(monkeypatch):
    """Every step of the program's cohort trainer sees the first half of
    its batch only."""
    import jax.numpy as jnp
    from repro.fl.client import ShardedCohortTrainer

    orig = ShardedCohortTrainer._sharded_train_raw

    def broken(self, use_prox, has_mask, load=None):
        train = orig(self, use_prox, has_mask, load)

        def wrapped(params, x, y, sw, sv, *rest):
            b = sw.shape[-1]
            return train(params, x, y, sw * (jnp.arange(b) < b // 2).astype(sw.dtype), sv, *rest)

        return wrapped

    monkeypatch.setattr(ShardedCohortTrainer, "_sharded_train_raw", broken)


@pytest.mark.parametrize("fault", [None, "half_batch"])
def test_tiny_deepseek_cell(tmp_path, monkeypatch, fault):
    c = ds_cell(tmp_path)
    if fault:
        half_batches(monkeypatch)
    out = run.run_cell(c, SEED, 1.0, False)
    print(out["checks"])
    assert out["correct"] == (fault is None), out["checks"]
    assert out["failed"] == 0
