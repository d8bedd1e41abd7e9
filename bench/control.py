#!/usr/bin/env python3
"""Readings of the control and of planted faults, at a cell's own size.

    python3 bench/control.py --workload emnist_cnn.paper --seeds 1,2,3

For each seed: the reference (as ``run.py`` computes it), and in the
program's place the control (the same reference in bfloat16) and the
reference with half of every batch left out; then the program itself with
its ingest broken underneath (every Omega row returned unchanged), over a
job of two chunks.  Prints one JSON line of numbers per seed and stand-in;
the upper readings of the limits in ``bench/limits/`` come from these.  Not
part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def unchanged_rows(ids, dots, last_rounds, t, omega_rows):
    """Alg. 1 that leaves every fresh Omega row as it was."""
    return omega_rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    c = run.load_cell(args.workload)
    try:
        run.check_chip(int(c.cell["chips"]))
    except run.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    run.use_compile_cache()

    import compare
    import traffic
    from reference import Reference, leaf_sizes, make_weights

    arch, cfg, mix = c.model, c.cfg, c.mix
    rounds = int(mix["check_rounds"])
    psi = float(mix["es_threshold"])
    counts = traffic.fixed_counts(arch, cfg, mix)
    weights = make_weights(arch, cfg)
    stand_ins = {"control_bf16": ("bfloat16", None), "half_batch": (None, "half_batch")}
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        drawn = run.derive_seeds(seed, mix)
        data = traffic.make_traffic(arch, cfg, mix, counts, drawn["data"])
        params, frozen = weights(drawn["init"], drawn["frozen"])
        sizes = leaf_sizes(params)
        t0 = time.perf_counter()
        want = Reference(arch, cfg, mix).run(data, params, frozen, drawn["select"],
                                             drawn["job"], rounds)
        t_ref = time.perf_counter() - t0
        for name, (precision, fault) in stand_ins.items():
            got = Reference(arch, cfg, mix, precision=precision, fault=fault).run(
                data, params, frozen, drawn["select"], drawn["job"], rounds)
            nums = compare.numbers(got, want, sizes, rounds, c.limits, psi)
            gaps = compare.client_gaps(got, want, sizes, rounds)
            print(json.dumps({"workload": args.workload, "seed": seed, "stand_in": name,
                              "reference_s": t_ref, "numbers": nums,
                              "clients": [{**g, "samples": int(data.sizes[g["client"]])}
                                          for g in gaps]}), flush=True)
    # the program, its ingest broken: a job of two chunks
    from repro.core import relationship

    relationship.rows_from_relationship_dots = unchanged_rows
    short = run.SimpleNamespace(**{**vars(c), "mix": {**mix, "min_window_chunks": 1}})
    for seed in seeds:
        out = run.run_cell(short, seed, 1.0, False)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "stand_in": "ingest_unchanged",
                          "numbers": {k: v["value"] for k, v in out["checks"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
