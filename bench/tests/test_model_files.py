"""A new architecture reaches a cell as files alone: a model module and its
config, here written into a temporary directory, run through
``run.run_cell`` with no harness file changed.

The module is a LoRA-style language model: token ids ride in float32
features, the token embedding and the output head are frozen weights made
by the module (outside the trained parameters), only the head's low-rank
factors train, through the program's ``LoRAClassifier``, and the module
states its own FLOPs a sample.
"""
import json
import re

import run
from test_correct import MIX, SEED, break_trainer, cell

MODULE = '''
"""A bigram language model whose head trains through LoRA factors."""
import jax
import jax.numpy as jnp
import numpy as np


def num_classes(cfg):
    return int(cfg["vocab_size"])


class BigramLM:
    """The program's base model: each token's embedding through the head
    predicts the next token; the last position's prediction is the label."""

    name = "bigram"

    def _logits(self, params, x):
        return params["embed"][x.astype(jnp.int32)] @ params["head"]["w"] + params["head"]["b"]

    def loss(self, params, x, y):
        labels = jnp.concatenate([x[:, 1:].astype(jnp.int32), y[:, None].astype(jnp.int32)], 1)
        logp = jax.nn.log_softmax(self._logits(params, x), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))

    def accuracy(self, params, x, y):
        hit = jnp.argmax(self._logits(params, x)[:, -1], axis=-1) == y
        return jnp.mean(hit.astype(jnp.float32))

    def flops_per_sample(self):
        return 0.0


def program_model(cfg, frozen):
    from repro.models.lora import LoRAClassifier

    return LoRAClassifier(BigramLM(), frozen, rank=int(cfg["lora_rank"]), targets=("w",))


def frozen(cfg, key):
    v, h = int(cfg["vocab_size"]), int(cfg["hidden_size"])
    k_embed, k_head = jax.random.split(key)
    return {"embed": jax.random.normal(k_embed, (v, h), jnp.float32),
            "head": {"w": jax.random.normal(k_head, (h, v), jnp.float32) / np.sqrt(h),
                     "b": jnp.zeros((v,), jnp.float32)}}


def init(cfg, key):
    """The head's factors, laid out as LoRAClassifier lays them out."""
    v, h, r = int(cfg["vocab_size"]), int(cfg["hidden_size"]), int(cfg["lora_rank"])
    return {"head/w": {"a": jax.random.normal(key, (h, r), jnp.float32) / np.sqrt(h),
                       "b": jnp.zeros((r, v), jnp.float32)}}


def sampler(cfg, mix, rng):
    v, n = int(cfg["vocab_size"]), int(cfg["seq_len"])

    def draw(labels):
        x = rng.integers(0, v, size=(len(labels), n))
        x[:, -1] = (labels + 1) % v
        return x.astype(np.float32)

    return draw


def _logits(params, frozen, x, dtype, prec):
    ab = jnp.dot(params["head/w"]["a"], params["head/w"]["b"], precision=prec)
    head = frozen["head"]["w"].astype(dtype) + ab
    h = frozen["embed"].astype(dtype)[x.astype(jnp.int32)]
    return (jnp.einsum("blh,hv->blv", h, head, precision=prec)
            + frozen["head"]["b"]).astype(jnp.float32)


def loss(cfg, params, frozen, x, y, w, dtype, prec):
    labels = jnp.concatenate([x[:, 1:].astype(jnp.int32), y[:, None]], axis=1)
    logp = jax.nn.log_softmax(_logits(params, frozen, x, dtype, prec), axis=-1)
    per = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0], axis=1)
    return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)


def correct(cfg, params, frozen, x, y, dtype, prec):
    last = _logits(params, frozen, x, dtype, prec)[:, -1]
    return jnp.sum((jnp.argmax(last, axis=-1) == y).astype(jnp.int32))


def flops_per_sample(cfg):
    """The head's multiply-adds at every position, forward and backward."""
    return 3.0 * 2 * int(cfg["hidden_size"]) * int(cfg["vocab_size"]) * int(cfg["seq_len"])
'''

CONFIG = {"name": "tiny_lm", "model": "TinyLM", "vocab_size": 32, "hidden_size": 16,
          "seq_len": 8, "lora_rank": 4}


def lm_cell(tmp_path):
    (tmp_path / "TinyLM.py").write_text(MODULE)
    (tmp_path / "tiny_lm.json").write_text(json.dumps(CONFIG))
    cfg = json.loads((tmp_path / "tiny_lm.json").read_text())
    c = cell()
    c.cell = {"name": "tiny_lm.cell", "chips": 1}
    c.cfg, c.model = cfg, run.load_model(cfg["model"], str(tmp_path))
    return c


def test_sound_run_is_correct(tmp_path):
    c = lm_cell(tmp_path)
    out = run.run_cell(c, SEED, 1.0, False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"round_s", "peak_hbm_gb", "setup_s"}
    found = re.search(r"model FLOPs in the window (\S+) over (\d+) samples trained",
                      "\n".join(out["notes"]))
    per_sample = c.model.flops_per_sample(c.cfg)
    assert float(found.group(1)) == per_sample * int(found.group(2)) * MIX["local_epochs"] > 0


def test_half_batch_is_not_correct(tmp_path, monkeypatch):
    c = lm_cell(tmp_path)
    break_trainer(monkeypatch, "half_batch")
    out = run.run_cell(c, SEED, 1.0, False)
    assert not out["correct"], out["checks"]
