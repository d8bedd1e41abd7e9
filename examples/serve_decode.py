"""Batched-request serving demo with the cached decode path.

    PYTHONPATH=src python examples/serve_decode.py --arch recurrentgemma-2b

Serves a REDUCED variant of the chosen architecture: a batch of prompts is
prefilled token-by-token and then decoded greedily, exercising every cache
kind (KV ring buffers, mLSTM matrix memory, RG-LRU state, whisper cross-KV).
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, list_archs
from repro.launch import compile_cache
from repro.launch.serve import generate
from repro.models import TransformerLM


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs(), default="recurrentgemma-2b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen", type=int, default=24)
    args = ap.parse_args()
    compile_cache()

    cfg = get_arch(args.arch, reduced=True)
    model = TransformerLM(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32
    )
    t0 = time.perf_counter()
    out = generate(model, params, prompts, args.gen, args.prompt_len + args.gen)
    dt = time.perf_counter() - t0
    new_tokens = args.batch * args.gen
    print(f"[serve] {cfg.name}: {args.batch} requests x {args.gen} new tokens "
          f"in {dt:.2f}s ({new_tokens / dt:.1f} tok/s on 1 CPU core)")
    for i in range(min(2, args.batch)):
        seq = np.asarray(out[i]).tolist()
        print(f"  request {i}: prompt={seq[:args.prompt_len]} -> "
              f"continuation={seq[args.prompt_len:args.prompt_len + 12]}...")


if __name__ == "__main__":
    main()
