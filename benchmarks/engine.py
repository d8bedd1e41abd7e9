"""Round-engine benchmark: sequential vs batched vs sharded vs scan driver.

The batched engine's claim (DESIGN.md §Engine) is that one fused device
program per round beats O(clients × steps) Python dispatches; the sharded
engine's claim is that the same round scales across a (data, model) mesh;
the scan driver's claim is that compiling whole round *chunks* into one
``lax.scan`` program removes the remaining per-round dispatch + host-sync
overhead.  This benchmark measures wall-clock per round for a 16-client ×
50-step cohort (n=800 samples/client, batch 32, 2 local epochs ⇒ 50 SGD
steps each) and writes machine-readable throughput to ``BENCH_engine.json``.

    PYTHONPATH=src python benchmarks/engine.py            # timed comparison
    PYTHONPATH=src python benchmarks/engine.py --smoke    # CI: short runs

The smoke mode also times a compressed-strategy leg (Fedcom, whose
device-resident top-k update transform runs inside the compiled chunk), so
``BENCH_engine.json`` tracks the transform overhead under the scan driver
(`batched_fedcom` / `scan_fedcom` entries), a `sharded_scan` leg
(driver="scan" × engine="sharded": the whole chunk fused on the mesh) timed
against the sharded loop engine over the same rounds
(`sharded_scan_speedup_vs_sharded`), and `pipelined` / `sharded_pipelined`
legs (the scan driver's two-deep chunk pipeline: next-chunk build + H2D +
dispatch overlapped with the current chunk's execution) timed against the
serial scan driver (`pipeline_speedup_vs_scan` /
`sharded_pipeline_speedup_vs_sharded_scan`) with record equivalence
asserted EXACTLY (same compiled program, only host scheduling differs), and
a `paged_fleet` leg (``client_store="paged"``: FedAvg at M=10⁴ vs M=10⁵
clients, asserting peak live device bytes stay flat within 10% — the paged
store's O(P_cand) device-memory contract — with the peaks and H2D page
traffic recorded under `paged_fleet`).
Every scan leg also reports its host/device time split from
``FLResult.driver_stats`` (`driver_stats` + `host_fraction` — the fraction
of wall time the host spent building/flushing rather than the device
computing), which is the quantity pipelining hides.

Force a real multi-device mesh on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the sharded engine
also runs — and is verified — on a single-device (1, 1) mesh).

Warmup/compile exclusion: each loop engine drops its first round; the scan
driver drops its first whole chunk (the chunk program compiles once) — via
``benchmarks.common.per_round_wall``, which all figure benchmarks share.
The acceptance bar (batched ≥2× sequential on CPU) is unchanged; the
sharded engine is reported, not gated — on host CPU the collectives are
emulated.  The scan driver's advantage is largest in the dispatch-bound
regime (small cohorts / short rounds — the CI smoke config); its magnitude
is host dependent (~1.5× on a 2-core container, ~3× with more idle cores),
so the smoke only warns if scan is ever SLOWER than the batched loop.  The
same applies to the pipeline: overlapping host and device work needs at
least two cores (`cpu_cores` is recorded in the report) — on a single-core
container the pipelined and serial drivers tie, on multi-core CI runners
the pipeline hides the host fraction and shows ≥1.2× in the dispatch-bound
smoke config.  On the compute-bound 16×50 cohort the jitted training
program is the floor and every gain is smaller.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

CLIENTS = 16
BATCH = 32
EPOCHS = 2
SAMPLES_PER_CLIENT = 800          # 800/32 * 2 epochs = 50 steps per client


def _dataset(num_clients: int, samples_per_client: int):
    from repro.data import make_federated_classification

    ds = make_federated_classification(
        num_clients=num_clients,
        alpha=1e6,                 # ~uniform: every client gets the same n,
        # so each trains exactly samples_per_client/BATCH * EPOCHS steps
        num_samples=num_clients * samples_per_client,
        num_eval=512,
        feature_dim=32,
        num_classes=10,
        seed=0,
    )
    return ds


def _fleet_dataset(m: int, n_per: int, feature_dim: int = 16, num_classes: int = 4):
    """A fleet-scale dataset built DIRECTLY — the Dirichlet partitioner's
    per-client Python work is O(M · classes), which at M=10⁵ would dominate
    the benchmark.  m clients × n_per identical-size tiny shards: total
    sample bytes scale with M, per-chunk cohort bytes do not."""
    from repro.data.synthetic import FederatedDataset

    rng = np.random.default_rng(7)
    n = m * n_per
    x = rng.standard_normal((n, feature_dim)).astype(np.float32)
    y = (np.arange(n) % num_classes).astype(np.int32)
    eval_x = rng.standard_normal((256, feature_dim)).astype(np.float32)
    eval_y = (np.arange(256) % num_classes).astype(np.int32)
    idx = np.arange(n, dtype=np.int64).reshape(m, n_per)
    return FederatedDataset(
        x=x, y=y, client_indices=[idx[k] for k in range(m)],
        eval_x=eval_x, eval_y=eval_y, num_classes=num_classes,
    )


def run(engine: str, ds, model, rounds: int, *, clients: int = CLIENTS,
        epochs: int = EPOCHS, driver: str = "loop", chunk: int = 8,
        warmup: int = 1, strategy_fn=None, pipeline=None,
        client_store: str = "resident", async_rounds=None):
    try:
        from benchmarks.common import per_round_wall
    except ImportError:
        # invoked as `python benchmarks/engine.py`: the repo root is not on
        # sys.path (only benchmarks/ is), so the package import needs it
        sys.path.insert(
            0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        from benchmarks.common import per_round_wall
    from repro.analysis.compile_guard import CompileCounter
    from repro.fl import run_federated
    from repro.fl.baselines import FedAvg

    if strategy_fn is None:
        strategy_fn = lambda: FedAvg(clients, clients, epochs, seed=0)
    t0 = time.perf_counter()
    with CompileCounter() as cc:
        res = run_federated(
            model, ds, strategy_fn(),
            max_rounds=rounds, learning_rate=0.05, batch_size=BATCH, seed=0,
            engine=engine, driver=driver, scan_chunk_rounds=chunk,
            pipeline=pipeline, client_store=client_store,
            async_rounds=async_rounds,
        )
    wall = time.perf_counter() - t0
    # every leg reports how many XLA programs it compiled (the recompile
    # sentinel); scan legs additionally carry driver_stats["compiles_chunk"]
    # schema pin: every leg's stats must match the published contract
    # (validated before the benchmark stamps its own bench_compiles extra —
    # the loop engines' `{}` stays empty and valid)
    from repro.fl.stats_schema import validate_driver_stats

    validate_driver_stats(res.driver_stats)
    res.driver_stats["bench_compiles"] = cc.compiles
    # exclude the compile-heavy warmup rounds (unless nothing would remain)
    per_round = per_round_wall(res, warmup)
    return res, wall, per_round


def _host_split(res) -> dict:
    """A scan leg's host/device wall partition from driver_stats.

    ``host_fraction`` is the share of total wall the host spent building
    schedules + dispatching and flushing records instead of waiting on the
    device — the serial overhead the pipeline overlaps away;
    ``device_stall_fraction`` is the share spent blocked in ``device_get``.
    """
    st = res.driver_stats
    if not st or not st.get("total_s"):
        return {}
    total = st["total_s"]
    return {
        "driver_stats": st,
        "host_fraction": (st["host_build_s"] + st["host_flush_s"]) / total,
        "device_stall_fraction": st["device_wait_s"] / total,
    }


def _leg_compiles(res) -> dict:
    """The leg's recompile-sentinel numbers for BENCH_engine.json: `total`
    XLA programs compiled during the leg, and for scan legs `chunk` — the
    compiles attributed to chunk dispatches (exactly 1 per job)."""
    st = res.driver_stats
    out = {"total": st.get("bench_compiles")}
    if "compiles_chunk" in st:
        out["chunk"] = st["compiles_chunk"]
    return out


def _assert_one_chunk_compile(res, leg: str) -> None:
    got = res.driver_stats.get("compiles_chunk")
    assert got == 1, (
        f"{leg}: expected exactly 1 chunk compile per job, observed {got} — "
        "a carry layout or candidate shape drifted between chunk dispatches "
        "(the silent-recompile regression PR 5's layout pinning prevents)")


def _assert_pipelined_identical(ser, pip, leg: str):
    """Pipelined ≡ serial must be EXACT: same compiled chunk program, same
    schedule streams — only the host's dispatch order differs."""
    assert pip.rounds_run == ser.rounds_run, leg
    assert [r.selected for r in ser.records] == \
           [r.selected for r in pip.records], leg
    assert [r.accuracy for r in ser.records] == \
           [r.accuracy for r in pip.records], leg
    assert [r.stopped for r in ser.records] == \
           [r.stopped for r in pip.records], leg
    assert ser.ledger.total_bytes == pip.ledger.total_bytes, leg
    assert ser.ledger.energy_j == pip.ledger.energy_j, leg


def _transformer_leg(chunk: int):
    """Federated transformer fine-tuning on the composed (data, model) mesh,
    roofline-grounded.

    Two runs of the same job (tiny ``ArchConfig`` through ``LMClassifier``,
    FedAvg cohorts, ``driver="scan", engine="sharded"`` on
    ``make_engine_mesh()``):

    1. the TIMED run, compile-sentinel-asserted (exactly one chunk compile —
       the model-axis sharding must not cost the pinned-layout discipline);
    2. an UNASSERTED capture run with ``repro.fl.scan_driver._hlo_capture``
       installed, whose compiled chunk HLO feeds ``roofline.hlo_stats``.

    The leg's payload compares the per-round per-device MEASURED dot FLOPs
    (from the post-partitioning HLO, while-trip-aware) and the EXPECTED
    model FLOPs (6·N·tokens) against the same analytic HBM traffic model
    (``fl_round_hbm_bytes`` — fp32 SGD, remat activation passes), and
    asserts both classify the training hot loop on the same side of the
    ``roofline.hw`` ridge: compute-bound exactly where the hardware model
    says it should be (the tiny smoke model sits far below the ridge, so
    both sides must say memory-bound — a measured "compute" here would mean
    the HLO is burning FLOPs the model doesn't ask for).
    """
    import jax

    import repro.fl.scan_driver as scan_driver
    from repro.configs.base import ATTN_GLOBAL, ArchConfig
    from repro.data import make_federated_lm
    from repro.fl.baselines import FedAvg
    from repro.models import LMClassifier
    from repro.roofline import fl_round_hbm_bytes, hw
    from repro.roofline.hlo_stats import analyze

    seq, vocab, cohort, m, n_per = 8, 64, 4, 8, 32
    cfg = ArchConfig(
        name="tiny-lm", family="bench", num_layers=2, d_model=16,
        num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=vocab,
        pattern=(ATTN_GLOBAL,), dtype="float32",
    )
    model = LMClassifier(cfg, seq_len=seq)
    ds = make_federated_lm(num_clients=m, samples_per_client=n_per,
                           seq_len=seq, vocab_size=vocab, num_eval=32)
    mk = lambda: FedAvg(m, cohort, 1, seed=0)
    rounds = 2 * chunk

    res, _, spr = run("sharded", ds, model, rounds, epochs=1, driver="scan",
                      chunk=chunk, warmup=chunk, strategy_fn=mk)
    assert res.rounds_run == rounds, res.rounds_run
    assert np.isfinite(res.final_accuracy), res.final_accuracy
    _assert_one_chunk_compile(res, "transformer")

    scan_driver._hlo_capture = captured = []
    try:
        run("sharded", ds, model, chunk, epochs=1, driver="scan",
            chunk=chunk, warmup=chunk, strategy_fn=mk)
    finally:
        scan_driver._hlo_capture = None
    assert captured, "transformer leg captured no chunk HLO"

    from repro.launch.mesh import make_engine_mesh

    chips = jax.device_count()       # make_engine_mesh() spans all devices
    data_shards = make_engine_mesh().shape["data"]
    st = analyze(captured[0], chips)
    local_steps = max(1, n_per // BATCH)
    hlo_flops_round = st.dot_flops / chunk            # per device, per round
    # activation-side dot work is sharded over the data axis only (rows are
    # replicated across the model axis), so the ideal per-device model FLOPs
    # divide by data_shards — same physics as the byte model below
    model_flops_round = (
        model.flops_per_sample() * n_per * cohort / data_shards
    )
    bytes_round = fl_round_hbm_bytes(
        cfg, seq_len=seq, batch=min(BATCH, n_per), local_steps=local_steps,
        cohort=cohort, chips=chips, data_shards=data_shards,
    )
    ridge = hw.PEAK_FLOPS_BF16 / hw.HBM_BW
    measured = hlo_flops_round / bytes_round
    expected = model_flops_round / bytes_round
    classify = lambda x: "compute" if x > ridge else "memory"
    assert classify(measured) == classify(expected), (
        f"transformer roofline disagrees with hw model: measured "
        f"{measured:.1f} FLOP/B vs expected {expected:.1f} FLOP/B around the "
        f"ridge {ridge:.1f} — the compiled chunk's arithmetic intensity is "
        "on the wrong side of the hardware model")
    payload = {
        "arch": cfg.name,
        "mesh_devices": chips,
        "hlo_dot_flops_per_round_per_device": hlo_flops_round,
        "model_flops_per_round_per_device": model_flops_round,
        "analytic_hbm_bytes_per_round_per_device": bytes_round,
        "flop_per_byte_measured": measured,
        "flop_per_byte_expected": expected,
        "ridge_flop_per_byte": ridge,
        "bottleneck": classify(measured),
        "collective_bytes_per_device": st.collective_bytes,
        "collective_by_kind": st.collective_by_kind,
    }
    return res, spr, payload


def write_report(path: str, per_round: dict, meta: dict,
                 compiles: dict = None) -> None:
    import jax

    from repro.fl.stats_schema import validate_bench_report

    compiles = compiles or {}
    report = {
        "benchmark": "engine",
        "devices": jax.device_count(),
        "backend": jax.default_backend(),
        **meta,
        "engines": {
            eng: {
                "s_per_round": s,
                "rounds_per_s": (1.0 / s if s > 0 else None),
                **({"compiles": compiles[eng]} if eng in compiles else {}),
            }
            for eng, s in per_round.items()
        },
    }
    # schema pin: a malformed report (renamed key, missing leg timing, bool
    # where a count belongs) fails HERE, not in whatever reads the JSON later
    validate_bench_report(report)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {path}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: assert short batched+sharded+scan runs complete")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--out", default="BENCH_engine.json",
                    help="machine-readable throughput report path")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    from repro.models.cnn import MLPClassifier

    compile_cache()

    model = MLPClassifier(feature_dim=32, num_classes=10, hidden=(64, 64))

    if args.smoke:
        ds = _dataset(4, 128)
        per_round = {}
        compiles = {}

        # scan driver leg: enough rounds for the per-chunk amortization to
        # show, against a batched run of the same length (timing + records)
        scan_rounds, chunk = 24, 8
        res_bat, _, per_round["batched"] = run(
            "batched", ds, model, scan_rounds, clients=4, epochs=1)
        assert res_bat.rounds_run == scan_rounds, res_bat.rounds_run
        assert np.isfinite(res_bat.final_accuracy), res_bat.final_accuracy
        assert res_bat.records[-1].evaluated
        res_scan, _, per_round["scan"] = run(
            "batched", ds, model, scan_rounds, clients=4, epochs=1,
            driver="scan", chunk=chunk, warmup=chunk, pipeline=False)
        assert res_scan.rounds_run == scan_rounds, res_scan.rounds_run
        _assert_one_chunk_compile(res_scan, "scan")
        assert [r.selected for r in res_bat.records] == \
               [r.selected for r in res_scan.records]
        assert abs(res_bat.final_accuracy - res_scan.final_accuracy) < 2e-3, (
            res_bat.final_accuracy, res_scan.final_accuracy)
        speedup = per_round["batched"] / per_round["scan"]

        # pipelined chunk driver: the same compiled chunks with next-chunk
        # build/H2D/dispatch overlapped against device execution.  Records
        # must equal the serial scan driver's EXACTLY.
        res_pip, _, per_round["pipelined"] = run(
            "batched", ds, model, scan_rounds, clients=4, epochs=1,
            driver="scan", chunk=chunk, warmup=chunk, pipeline=True)
        _assert_pipelined_identical(res_scan, res_pip, "pipelined")
        assert res_pip.driver_stats["speculative_chunks"] > 0
        _assert_one_chunk_compile(res_pip, "pipelined")
        speedup_pip = per_round["scan"] / per_round["pipelined"]
        host_split = {
            "scan": _host_split(res_scan),
            "pipelined": _host_split(res_pip),
        }

        # mesh-sharded compiled chunks: driver="scan" x engine="sharded".
        # The sharded loop pays a Python round trip + per-round shard_map
        # dispatches; fusing whole chunks on the mesh removes both.  Timed
        # against the sharded loop over the same rounds (records asserted
        # equivalent + batched ≡ sharded accuracy), with speedup-vs-sharded
        # recorded in BENCH_engine.json.
        res_shl, _, per_round["sharded"] = run(
            "sharded", ds, model, scan_rounds, clients=4, epochs=1)
        assert abs(res_bat.final_accuracy - res_shl.final_accuracy) < 2e-3, (
            res_bat.final_accuracy, res_shl.final_accuracy)
        res_shs, _, per_round["sharded_scan"] = run(
            "sharded", ds, model, scan_rounds, clients=4, epochs=1,
            driver="scan", chunk=chunk, warmup=chunk, pipeline=False)
        assert res_shs.rounds_run == scan_rounds, res_shs.rounds_run
        _assert_one_chunk_compile(res_shs, "sharded_scan")
        assert [r.selected for r in res_shl.records] == \
               [r.selected for r in res_shs.records]
        assert abs(res_shl.final_accuracy - res_shs.final_accuracy) < 2e-3, (
            res_shl.final_accuracy, res_shs.final_accuracy)
        assert res_shl.ledger.total_bytes == res_shs.ledger.total_bytes
        speedup_sh = per_round["sharded"] / per_round["sharded_scan"]

        # sharded pipeline: the donated D-sharded carries alternate between
        # the two in-flight chunk programs, sharded schedule uploads double-
        # buffer — records must still equal the serial sharded chunks exactly
        res_shp, _, per_round["sharded_pipelined"] = run(
            "sharded", ds, model, scan_rounds, clients=4, epochs=1,
            driver="scan", chunk=chunk, warmup=chunk, pipeline=True)
        _assert_pipelined_identical(res_shs, res_shp, "sharded_pipelined")
        _assert_one_chunk_compile(res_shp, "sharded_pipelined")
        speedup_shp = per_round["sharded_scan"] / per_round["sharded_pipelined"]
        host_split["sharded_scan"] = _host_split(res_shs)
        host_split["sharded_pipelined"] = _host_split(res_shp)

        # compressed-strategy leg: the device-resident update transform
        # (Fedcom top-k through the Pallas row kernel) must not cost the scan
        # driver its advantage — BENCH_engine.json tracks the overhead
        from repro.fl.baselines import Fedcom

        mk_fedcom = lambda: Fedcom(4, 4, 1, seed=0, keep_frac=0.25)
        res_bat_c, _, per_round["batched_fedcom"] = run(
            "batched", ds, model, scan_rounds, clients=4, epochs=1,
            strategy_fn=mk_fedcom)
        res_scan_c, _, per_round["scan_fedcom"] = run(
            "batched", ds, model, scan_rounds, clients=4, epochs=1,
            driver="scan", chunk=chunk, warmup=chunk, strategy_fn=mk_fedcom)
        assert res_scan_c.rounds_run == scan_rounds, res_scan_c.rounds_run
        _assert_one_chunk_compile(res_scan_c, "scan_fedcom")
        assert [r.selected for r in res_bat_c.records] == \
               [r.selected for r in res_scan_c.records]
        assert abs(res_bat_c.final_accuracy - res_scan_c.final_accuracy) < 2e-3, (
            res_bat_c.final_accuracy, res_scan_c.final_accuracy)
        assert res_bat_c.ledger.total_bytes == res_scan_c.ledger.total_bytes, (
            res_bat_c.ledger.total_bytes, res_scan_c.ledger.total_bytes)
        speedup_c = per_round["batched_fedcom"] / per_round["scan_fedcom"]
        # staleness-aware async rounds: the same compiled chunks with the
        # arrival ring buffer riding in the donated carry.  The leg pins the
        # two invariants benchmarking can check cheaply: the async chunk
        # still compiles exactly once (the ring buffer must not break the
        # pinned carry layout), and resource charges stay departure-based
        # (energy/bytes equal the synchronous scan leg's at any staleness).
        from repro.fl import AsyncConfig

        res_async, _, per_round["async"] = run(
            "batched", ds, model, scan_rounds, clients=4, epochs=1,
            driver="scan", chunk=chunk, warmup=chunk, pipeline=True,
            async_rounds=AsyncConfig(max_staleness=2))
        assert res_async.rounds_run == scan_rounds, res_async.rounds_run
        _assert_one_chunk_compile(res_async, "async")
        st_async = res_async.driver_stats
        departures = sum(len(r.selected) for r in res_async.records)
        assert st_async["async_arrivals"] + st_async["async_pending_at_exit"] \
            == departures, (st_async, departures)
        assert res_async.ledger.total_bytes == res_scan.ledger.total_bytes
        assert res_async.ledger.energy_j == res_scan.ledger.energy_j
        host_split["async"] = _host_split(res_async)

        compiles.update({
            "batched": _leg_compiles(res_bat),
            "scan": _leg_compiles(res_scan),
            "async": _leg_compiles(res_async),
            "pipelined": _leg_compiles(res_pip),
            "sharded": _leg_compiles(res_shl),
            "sharded_scan": _leg_compiles(res_shs),
            "sharded_pipelined": _leg_compiles(res_shp),
            "batched_fedcom": _leg_compiles(res_bat_c),
            "scan_fedcom": _leg_compiles(res_scan_c),
        })

        # fleet-scale paged store: client_store="paged" keeps the (M, N_max,
        # …) universe HOST-side and pages only each chunk's candidate rows,
        # so peak live device bytes must stay FLAT as the fleet grows 10x —
        # M=10k vs M=100k within 10% (the acceptance bar; everything on the
        # device is O(P_cand), never O(M))
        import gc

        from repro.fl.baselines import FedAvg

        def fleet_leg(m_fleet: int):
            gc.collect()
            ds_f = _fleet_dataset(m_fleet, 4)
            model_f = MLPClassifier(feature_dim=16, num_classes=4, hidden=(32,))
            mk = lambda: FedAvg(m_fleet, 8, 1, seed=0)
            res, _, spr = run(
                "batched", ds_f, model_f, 8, clients=8, epochs=1,
                driver="scan", chunk=4, warmup=4, strategy_fn=mk,
                client_store="paged")
            assert res.rounds_run == 8, res.rounds_run
            assert np.isfinite(res.final_accuracy), res.final_accuracy
            st = res.driver_stats
            assert st["store"] == "paged" and st["peak_live_bytes"] > 0
            assert st["page_bytes_h2d"] > 0
            _assert_one_chunk_compile(res, f"paged_fleet M={m_fleet}")
            return spr, st, _leg_compiles(res)

        per_round["paged_fleet_10k"], st_10k, compiles["paged_fleet_10k"] = \
            fleet_leg(10_000)
        per_round["paged_fleet_100k"], st_100k, compiles["paged_fleet_100k"] = \
            fleet_leg(100_000)
        peak_10k = st_10k["peak_live_bytes"]
        peak_100k = st_100k["peak_live_bytes"]
        peak_ratio = peak_100k / max(peak_10k, 1)
        assert abs(peak_ratio - 1.0) <= 0.10, (
            f"paged store device memory not flat in M: peak {peak_10k} B at "
            f"M=10k vs {peak_100k} B at M=100k ({peak_ratio:.3f}x)")
        paged_fleet = {
            "m_small": 10_000, "m_large": 100_000,
            "peak_live_bytes_10k": peak_10k,
            "peak_live_bytes_100k": peak_100k,
            "peak_ratio_100k_vs_10k": peak_ratio,
            "page_bytes_h2d_100k": st_100k["page_bytes_h2d"],
            "schedule_bytes_host_100k": st_100k["schedule_bytes_host"],
        }

        # federated transformer fine-tuning on the composed (data, model)
        # mesh, with the per-round FLOP/byte roofline report from the
        # compiled chunk's HLO (see _transformer_leg)
        res_tf, per_round["transformer"], tf_roofline = _transformer_leg(chunk)
        compiles["transformer"] = _leg_compiles(res_tf)
        host_split["transformer"] = _host_split(res_tf)

        write_report(args.out, per_round,
                     {"mode": "smoke", "clients": 4, "steps": 4,
                      "scan_chunk_rounds": chunk,
                      "transformer_roofline": tf_roofline,
                      "cpu_cores": len(os.sched_getaffinity(0)),
                      "scan_speedup_vs_batched": speedup,
                      "scan_speedup_vs_batched_fedcom": speedup_c,
                      "sharded_scan_speedup_vs_sharded": speedup_sh,
                      "pipeline_speedup_vs_scan": speedup_pip,
                      "sharded_pipeline_speedup_vs_sharded_scan": speedup_shp,
                      "async_max_staleness": 2,
                      "paged_fleet": paged_fleet,
                      "host_split": host_split},
                     compiles=compiles)
        print(f"transformer roofline: "
              f"{tf_roofline['flop_per_byte_measured']:.2f} FLOP/B measured vs "
              f"{tf_roofline['flop_per_byte_expected']:.2f} expected "
              f"(ridge {tf_roofline['ridge_flop_per_byte']:.0f}, "
              f"{tf_roofline['bottleneck']}-bound)")
        print(f"engine-smoke OK: batched+sharded+scan+sharded_scan+pipelined, "
              f"acc={res_bat.final_accuracy:.3f}, scan {speedup:.2f}x batched, "
              f"fedcom scan {speedup_c:.2f}x batched, "
              f"sharded_scan {speedup_sh:.2f}x sharded, "
              f"pipelined {speedup_pip:.2f}x scan, "
              f"sharded_pipelined {speedup_shp:.2f}x sharded_scan, "
              f"paged_fleet peak 100k/10k {peak_ratio:.3f}x, "
              f"host_fraction(scan)="
              f"{host_split['scan'].get('host_fraction', 0):.2f}")
        # regression signal: the scan driver must never be SLOWER than the
        # batched loop it replaces.  The magnitude of the win is host
        # dependent (measured ~1.5x on a 2-core container, ~3x with more
        # cores — dispatch overlap needs idle cores), so only <1x warns.
        if speedup < 1.0:
            print("WARNING: scan driver slower than the batched loop on the "
                  "smoke config", file=sys.stderr)
        if speedup_c < 1.0:
            print("WARNING: compressed-strategy scan slower than the batched "
                  "loop on the smoke config", file=sys.stderr)
        if speedup_sh < 1.0:
            print("WARNING: sharded compiled chunks slower than the sharded "
                  "loop on the smoke config", file=sys.stderr)
        # the pipeline needs a core for the host while the device computes:
        # on a single-core container the two drivers tie (the overlap has
        # nowhere to run), so the ≥1.2x expectation only applies multi-core
        if speedup_pip < 1.0:
            print("WARNING: pipelined chunk driver slower than the serial "
                  "scan driver on the smoke config", file=sys.stderr)
        elif speedup_pip < 1.2 and len(os.sched_getaffinity(0)) > 1:
            print(f"WARNING: pipelined speedup {speedup_pip:.2f}x below the "
                  "1.2x multi-core expectation", file=sys.stderr)
        if speedup_shp < 1.0:
            print("WARNING: sharded pipelined chunks slower than the serial "
                  "sharded chunks on the smoke config", file=sys.stderr)
        return 0

    ds = _dataset(CLIENTS, SAMPLES_PER_CLIENT)
    steps = SAMPLES_PER_CLIENT // BATCH * EPOCHS
    print(f"cohort: {CLIENTS} clients x {steps} steps (batch {BATCH})")

    per_round = {}
    for engine in ("sequential", "batched", "sharded"):
        _, _, per_round[engine] = run(engine, ds, model, args.rounds)
        print(f"{engine + ':':12s}{per_round[engine] * 1e3:8.1f} ms/round")
    # scan driver: chunks of args.rounds; the first chunk is compile warmup
    res_scan, _, per_round["scan"] = run(
        "batched", ds, model, args.rounds * 3, driver="scan",
        chunk=args.rounds, warmup=args.rounds, pipeline=False)
    _assert_one_chunk_compile(res_scan, "scan")
    print(f"{'scan:':12s}{per_round['scan'] * 1e3:8.1f} ms/round")
    res_pip, _, per_round["pipelined"] = run(
        "batched", ds, model, args.rounds * 3, driver="scan",
        chunk=args.rounds, warmup=args.rounds, pipeline=True)
    _assert_pipelined_identical(res_scan, res_pip, "pipelined")
    _assert_one_chunk_compile(res_pip, "pipelined")
    print(f"{'pipelined:':12s}{per_round['pipelined'] * 1e3:8.1f} ms/round")
    speedup = per_round["sequential"] / per_round["batched"]
    print(f"batched speedup: {speedup:8.2f}x")
    print(f"sharded vs batched: "
          f"{per_round['batched'] / per_round['sharded']:8.2f}x")
    print(f"scan vs batched: "
          f"{per_round['batched'] / per_round['scan']:8.2f}x")
    print(f"pipelined vs scan: "
          f"{per_round['scan'] / per_round['pipelined']:8.2f}x")
    write_report(args.out, per_round,
                 {"mode": "timed", "clients": CLIENTS, "steps": steps,
                  "scan_chunk_rounds": args.rounds,
                  "cpu_cores": len(os.sched_getaffinity(0)),
                  "scan_speedup_vs_batched":
                      per_round["batched"] / per_round["scan"],
                  "pipeline_speedup_vs_scan":
                      per_round["scan"] / per_round["pipelined"],
                  "host_split": {"scan": _host_split(res_scan),
                                 "pipelined": _host_split(res_pip)}},
                 compiles={"scan": _leg_compiles(res_scan),
                           "pipelined": _leg_compiles(res_pip)})
    if speedup < 2.0:
        print("WARNING: batched engine below the 2x acceptance bar", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
