"""End-to-end driver: cross-silo federated pretraining of a transformer LM
with FLrce server-side control (the framework-scale version of the paper).

    # ~20M-param model, quick demo (default)
    PYTHONPATH=src python examples/federated_pretrain.py

    # ~100M-param model, a few hundred local steps total (CPU: hours)
    PYTHONPATH=src python examples/federated_pretrain.py --size 100m --rounds 25

Each silo draws from its own topic-skewed Zipf-Markov token stream; the
whole job runs through ``run_federated(driver="scan", engine="sharded")`` —
the compiled path: local SGD, Eq. 4 aggregation, relationship modeling over
the deltas (Alg. 1), explore/exploit selection (Alg. 2) and the
conflict-based early stop (Alg. 3) all execute inside one ``lax.scan``
chunk program per ``--chunk`` rounds, shard_mapped over the composed
``(data, model)`` mesh (a ``(1, 1)`` mesh on a single device; force 8 with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  The model-axis
sharding comes from ``sharding/policy.py`` via ``LMClassifier.param_specs``.
"""
import argparse
import json
import time

import jax
import numpy as np

from repro.configs.base import ATTN_GLOBAL, ArchConfig
from repro.data import make_federated_lm
from repro.fl import FLrce, run_federated
from repro.launch import compile_cache
from repro.models import LMClassifier
from repro.models.cnn import param_count

SIZES = {
    # name: (layers, d_model, heads, d_ff, vocab) — approx param counts
    "5m": (4, 128, 4, 512, 4096),
    "20m": (6, 256, 8, 1024, 16_384),
    "100m": (16, 512, 8, 2048, 32_768),
}


def make_cfg(size: str) -> ArchConfig:
    nl, d, h, f, v = SIZES[size]
    return ArchConfig(
        name=f"fedlm-{size}", family="dense", num_layers=nl, d_model=d,
        num_heads=h, num_kv_heads=h, d_ff=f, vocab_size=v,
        pattern=(ATTN_GLOBAL,), norm="rmsnorm", act="silu", gated_mlp=True,
        dtype="float32",
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--size", choices=sorted(SIZES), default="20m")
    ap.add_argument("--silos", type=int, default=8)
    ap.add_argument("--participants", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=12)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--psi", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache()

    cfg = make_cfg(args.size)
    model = LMClassifier(cfg, seq_len=args.seq)
    dim = param_count(model.init(jax.random.PRNGKey(args.seed)))
    print(f"[fedlm] {cfg.name}: {dim:,} params, {args.silos} silos, "
          f"{args.participants}/round, {args.rounds} rounds, "
          f"{jax.device_count()} device(s)")

    # one local epoch over batch*local_steps samples/silo = --local-steps
    # SGD steps per selected silo per round, as in the hand-rolled loop
    ds = make_federated_lm(
        num_clients=args.silos, samples_per_client=args.batch * args.local_steps,
        seq_len=args.seq, vocab_size=cfg.vocab_size, num_eval=8 * args.batch,
        alpha=0.25, seed=args.seed,
    )
    psi = args.psi if args.psi is not None else args.participants / 2
    strategy = FLrce(args.silos, args.participants, 1, dim=dim,
                     es_threshold=psi, explore_decay=0.85, seed=args.seed)

    t0 = time.perf_counter()
    res = run_federated(
        model, ds, strategy,
        max_rounds=args.rounds, learning_rate=args.lr, batch_size=args.batch,
        seed=args.seed, engine="sharded", driver="scan",
        scan_chunk_rounds=args.chunk,
    )
    wall = time.perf_counter() - t0

    for rec in res.records:
        print(json.dumps({
            "round": rec.t, "silos": [int(i) for i in rec.selected],
            "accuracy": round(float(rec.accuracy), 4),
            "mean_loss": round(float(rec.mean_client_loss), 4),
            "exploit": bool(rec.exploited), "stopped": bool(rec.stopped),
        }))
    if res.stopped_early:
        print(f"[fedlm] early stop at round {res.rounds_run - 1} "
              f"(psi={psi}) — saved {args.rounds - res.rounds_run} rounds")
    print(f"[fedlm] done: {res.rounds_run} rounds in {wall:.1f}s "
          f"({res.driver_stats.get('compiles_chunk', '?')} chunk compile(s)), "
          f"next-token acc {float(res.final_accuracy):.4f}, "
          f"uploaded {res.ledger.bytes_up / 2**20:.1f} MiB")


if __name__ == "__main__":
    main()
