"""The harness reads, through the CNN's model module, what it read when the
CNN's code was written into its shared files: the same traffic, initial
weights and reference rounds, byte for byte.

Each constant is a SHA-256 digest (``digest`` and ``reference_digest``
below) taken from commit 1fa0d93, the harness before its model code moved
into ``bench/models/PaperCNN.py``: that commit's tree unpacked with
``git archive``, and these functions run over its ``traffic.make_traffic``,
``reference.make_init`` and ``Reference(...).run`` at the same sizes and
seed, on the CPU backend (``JAX_PLATFORMS=cpu``, jax 0.9.0).  The digests
come out the same on 2 CPU cores and on 8.
"""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

import run
import traffic
from reference import Reference, make_weights
from test_correct import CFG, MIX, SEED

EXPECTED = {
    "tiny": {
        "traffic": "8f2e20e8dd16346cf8556ecf5e52761b245f19954919d56e05ab50c32e9b0205",
        "weights": "6f57493fb38258d9ca33c6de9ac3e89ba29d25856bad3de21163fa3cbc44c7d2",
        "reference_float32": "f16cecb00544104d8fee2af35c4e6aa8a4ad936791506c4bfb1259ac86875c85",
        "reference_bfloat16": "7dd2f3d2a2253da7819b0a12afa255c43c7029fc4919352862a46939d5cddc10",
    },
    # emnist_cnn.json's widths, emnist_paper.json with 1,200 samples and 300
    # eval samples (the cut keeps the CPU reference to seconds), without its
    # later selection_seed
    "emnist": {
        "traffic": "5159f889140d36a32358067174eea94f3e49a385e5b9311deea09742541e0ea3",
        "weights": "e3e625de45bab99edb46ce2f4c4f6de34438fb9c874bb6f01f5bc48700a751b7",
        "reference_float32": "72f575980c1496dda7bdad2dceb6ff110fe49c4d013dade99f91b2ae7acaa255",
    },
}

PRECISIONS = {"reference_float32": None, "reference_bfloat16": "bfloat16"}


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def reference_digest(out) -> str:
    """The recorded rounds (cohorts, flags, losses, accuracies, margins,
    pair counts), then every model, update and Omega row by key, then H."""
    rounds = json.dumps(out["rounds"], sort_keys=True).encode()
    arrays = [out[part][k] for part in ("models", "updates", "omega") for k in sorted(out[part])]
    heur = np.asarray([out["heuristic"][k] for k in sorted(out["heuristic"])], np.float64)
    return digest(np.frombuffer(rounds, np.uint8), *arrays, heur)


def emnist():
    bench = os.path.dirname(traffic.__file__)
    with open(os.path.join(bench, "configs", "emnist_cnn.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "workloads", "emnist_paper.json")) as f:
        mix = json.load(f)
    # the mix as that commit read it: its selection_seed came later
    mix = {k: v for k, v in mix.items() if k != "selection_seed"}
    return cfg, {**mix, "num_samples": 1200, "num_eval": 300}


@pytest.fixture(scope="module")
def reads():
    out = {}
    for size, (cfg, mix) in {"tiny": (CFG, MIX), "emnist": emnist()}.items():
        seeds = run.derive_seeds(SEED)
        model = run.load_model(cfg["model"])
        counts = traffic.fixed_counts(model, cfg, mix)
        data = traffic.make_traffic(model, cfg, mix, counts, seeds["data"])
        params, frozen = make_weights(model, cfg)(seeds["init"], seeds["frozen"])
        got = {
            "traffic": digest(data.x, data.y, data.eval_x, data.eval_y, data.sizes,
                              np.concatenate(data.client_indices)),
            "weights": digest(*jax.tree_util.tree_leaves(params)),
        }
        for part, precision in PRECISIONS.items():
            if part in EXPECTED[size]:
                ref = Reference(model, cfg, mix, precision=precision).run(
                    data, params, frozen, seeds["select"], seeds["job"], int(mix["check_rounds"]))
                got[part] = reference_digest(ref)
        out[size] = got
    return out


@pytest.mark.parametrize("size,part", [(s, p) for s in EXPECTED for p in EXPECTED[s]])
def test_reads_what_it_read(reads, size, part):
    assert reads[size][part] == EXPECTED[size][part]
