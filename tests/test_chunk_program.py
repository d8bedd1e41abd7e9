"""What the compiled round chunk holds.

* A LoRA model's frozen base enters the chunk as an argument: the compiled
  program holds no constant over 1 MiB, and takes the base's weight as a
  parameter (with the base closed over instead, the same check finds it).
* The paper CNN, which has no frozen weights, compiles to the same chunk
  program as before frozen bases were threaded through (the program text,
  without its source metadata, against a digest taken from the earlier
  program).
"""
import hashlib
import re

import jax
import numpy as np
import pytest

from repro.data import make_federated_classification
from repro.fl import FLrce, run_federated, scan_driver
from repro.launch import make_engine_mesh
from repro.models import LoRAClassifier
from repro.models.cnn import MLPClassifier, PaperCNN, param_count

BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "pred": 1, "s8": 1, "u8": 1}
CONSTANT = re.compile(r"= (\w+)\[([\d,]*)\]\S* constant\(")
PARAMETER = re.compile(r"%frozen\S* = (\w+)\[([\d,]*)\]\S* parameter\(")


def _bytes(dtype, dims):
    return BYTES.get(dtype, 4) * int(np.prod([int(d) for d in dims.split(",") if d]))


def compiled_chunks(model, ds, dim, rounds=2):
    """The compiled chunk programs' text of a short FLrce job."""
    scan_driver._hlo_capture = []
    try:
        run_federated(model, ds, FLrce(num_clients=6, clients_per_round=2, local_epochs=1,
                                       dim=dim, use_early_stopping=False, seed=0),
                      max_rounds=rounds, learning_rate=0.01, batch_size=4, seed=0,
                      engine="sharded", mesh=make_engine_mesh(1, 1), driver="scan",
                      scan_chunk_rounds=rounds)
        return list(scan_driver._hlo_capture)
    finally:
        scan_driver._hlo_capture = None


@pytest.mark.parametrize("base", ["argument", "closure"])
def test_frozen_base_is_an_argument_not_a_constant(monkeypatch, base):
    feat = 1024                      # the base's (1024, 1024) f32 weight is 4 MiB
    ds = make_federated_classification(num_clients=6, alpha=0.5, num_samples=60, num_eval=12,
                                       feature_dim=feat, num_classes=3, seed=0)
    mlp = MLPClassifier(feature_dim=feat, num_classes=3, hidden=(feat,))
    lora = LoRAClassifier(mlp, mlp.init(jax.random.PRNGKey(0)), rank=2)
    if base == "closure":
        monkeypatch.setattr(LoRAClassifier, "frozen", property(lambda self: None))
    (text,) = compiled_chunks(lora, ds, lora.adapter_dim())
    biggest = max((_bytes(*m.groups()) for m in CONSTANT.finditer(text)), default=0)
    weight = any(m.groups() == ("f32", f"{feat},{feat}") for m in PARAMETER.finditer(text))
    if base == "argument":
        assert biggest <= 1 << 20, biggest
        assert weight
    else:
        assert biggest >= feat * feat * 4 and not weight


def normalized(text):
    """The program's instructions without source metadata (file names,
    line numbers and the stack-frame tables)."""
    keep = [l for l in text.splitlines() if l.startswith(("HloModule", "ENTRY", "%", "  ", "}"))]
    return "\n".join(re.sub(r", metadata=\{[^}]*\}", "", l) for l in keep)


# sha256 of ``normalized`` chunk text of the job below, taken from the
# program before frozen weights reached the chunk as an argument
CNN_CHUNK_SHA256 = "93215341c685f658"


def test_cnn_chunk_program_is_unchanged():
    ds = make_federated_classification(num_clients=6, alpha=0.5, num_samples=120, num_eval=20,
                                       feature_dim=64, num_classes=4, seed=0)
    ds = ds.__class__(**{**ds.__dict__, "x": ds.x.reshape(-1, 8, 8, 1),
                         "eval_x": ds.eval_x.reshape(-1, 8, 8, 1)})
    model = PaperCNN(side=8, channels=1, num_classes=4, num_fc=1, conv_channels=(4, 8),
                     fc_width=16)
    (text,) = compiled_chunks(model, ds, param_count(model.init(jax.random.PRNGKey(0))))
    assert hashlib.sha256(normalized(text).encode()).hexdigest()[:16] == CNN_CHUNK_SHA256
