"""Mesh-sharded compiled round chunks (driver="scan" × engine="sharded").

The sharded chunk program must reproduce the sharded *loop* engine's records
exactly where the loop is exact (selection sequences, exploited flags, stop
rounds, evaluation schedule, per-round ledger charges) and within fp32
tolerance elsewhere (accuracies, losses) — for FLrce and the
``supports_sharded_scan`` baselines, on the degenerate (1, 1) auto mesh
(runs everywhere) and on a real (2, 4) mesh (8 virtual CPU devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; those tests skip
cleanly with fewer devices).

The default fixture config deliberately covers the padding paths inside the
compiled chunk: the MLP's flat dim (195) is not divisible by the 8 D-shards
and the cohort (P=3) is not divisible by the mesh ``data`` axis (2).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from equivalence import assert_runs_equivalent
from repro.data import make_federated_classification
from repro.fl import FLrce, run_federated
from repro.fl.baselines import Dropout, FedAvg, Fedcom, Fedprox, TimelyFL
from repro.launch.mesh import make_debug_mesh
from repro.models.cnn import MLPClassifier, param_count

MULTI = jax.device_count() >= 8


def needs8(fn):
    """8-device-only test: skips without the forced host-device flag and
    carries the `multidevice` marker for the CI test-matrix split."""
    skip = pytest.mark.skipif(
        not MULTI,
        reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
    )
    return pytest.mark.multidevice(skip(fn))


@pytest.fixture(scope="module")
def mesh8():
    return make_debug_mesh(2, 4)


@pytest.fixture(scope="module")
def tiny_fed():
    ds = make_federated_classification(
        num_clients=8, alpha=0.2, num_samples=800, num_eval=160,
        feature_dim=8, num_classes=3, seed=2,
    )
    return ds, MLPClassifier(feature_dim=8, num_classes=3, hidden=(16,))


def _run_both(model, ds, make_strategy, *, mesh=None, chunk=2, **kw):
    mesh_kw = {"mesh": mesh} if mesh is not None else {}
    loo = run_federated(model, ds, make_strategy(), engine="sharded", **mesh_kw, **kw)
    scn = run_federated(
        model, ds, make_strategy(), engine="sharded", driver="scan",
        scan_chunk_rounds=chunk, **mesh_kw, **kw,
    )
    return loo, scn


def _assert_records_match(loo, scn):
    assert_runs_equivalent(loo, scn, bitwise=False)


def _strategies(dim):
    return [
        ("fedavg", lambda: FedAvg(8, 3, 2, seed=0)),
        ("fedprox", lambda: Fedprox(8, 3, 2, seed=0, mu=0.01)),
        ("flrce", lambda: FLrce(8, 3, 2, dim=dim, es_threshold=2.0, seed=0)),
    ]


# ---------------------------------------------------------------------------
# (1, 1) auto mesh: the sharded chunk code paths run on a single device
# ---------------------------------------------------------------------------
def test_sharded_scan_matches_sharded_loop_default_mesh(tiny_fed):
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    for name, mk in _strategies(dim):
        loo, scn = _run_both(
            model, ds, mk, max_rounds=4, learning_rate=0.1, batch_size=16,
            seed=0, chunk=3,
        )
        _assert_records_match(loo, scn)


# ---------------------------------------------------------------------------
# 8-device mesh: equivalence, padding exactness, mid-chunk ES, alignment
# ---------------------------------------------------------------------------
@needs8
@pytest.mark.parametrize("name", ["fedavg", "fedprox", "flrce"])
def test_sharded_scan_matches_sharded_loop_8dev(tiny_fed, mesh8, name):
    """D % 8 != 0 (dim 195 → D_pad 200) and P=3 % data=2 != 0: the padding
    paths inside the compiled chunk must be exact, not just close."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    assert dim % 8 != 0 and 3 % mesh8.shape["data"] != 0
    mk = dict(_strategies(dim))[name]
    loo, scn = _run_both(
        model, ds, mk, mesh=mesh8, max_rounds=5, learning_rate=0.1,
        batch_size=16, seed=0, chunk=2,
    )
    _assert_records_match(loo, scn)


@needs8
def test_sharded_scan_mid_chunk_es_stop(tiny_fed, mesh8):
    """A stop firing mid-chunk freezes the mesh-resident carry: flushed
    records, stop round and the written-back server state all match the
    sharded loop's early exit, and the V/A maps stay D-sharded."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    mk = lambda: FLrce(8, 3, 1, dim=dim, es_threshold=1e-6,
                       explore_decay=0.01, seed=0)
    loo = run_federated(model, ds, mk(), engine="sharded", mesh=mesh8,
                        max_rounds=40, learning_rate=0.8, batch_size=16, seed=0)
    strat = mk()
    scn = run_federated(model, ds, strat, engine="sharded", mesh=mesh8,
                        driver="scan", scan_chunk_rounds=8,
                        max_rounds=40, learning_rate=0.8, batch_size=16, seed=0)
    assert loo.stopped_early and scn.stopped_early
    assert loo.rounds_run < 40
    _assert_records_match(loo, scn)
    assert scn.records[-1].stopped and scn.records[-1].evaluated
    # the chunk carry really lived on the mesh: after write-back every device
    # holds a D-shard of the V map, none the full padded dim
    server = strat.server
    assert server.mesh is mesh8
    shards = server.state.updates.addressable_shards
    assert len({s.device for s in shards}) == 8
    assert all(s.data.shape[1] == server.dim_pad // 8 for s in shards)


@needs8
@pytest.mark.parametrize("chunk", [1, 3, 5, 8])
def test_sharded_scan_chunk_alignment_invariance(tiny_fed, mesh8, chunk):
    """Round results must not depend on how rounds are chunked (tail chunk
    shorter than chunk_rounds, chunk > max_rounds) on the real mesh."""
    ds, model = tiny_fed
    res = run_federated(
        model, ds, FedAvg(8, 3, 1, seed=0), engine="sharded", mesh=mesh8,
        driver="scan", scan_chunk_rounds=chunk,
        max_rounds=5, learning_rate=0.1, batch_size=16, seed=0,
    )
    ref = run_federated(
        model, ds, FedAvg(8, 3, 1, seed=0), engine="sharded", mesh=mesh8,
        max_rounds=5, learning_rate=0.1, batch_size=16, seed=0,
    )
    _assert_records_match(ref, res)


@needs8
def test_sharded_scan_final_w_stays_d_sharded(tiny_fed, mesh8):
    """The flat carry is D-sharded on entry and on exit of every chunk —
    run one job and check the final params reconstruct exactly from the
    sharded loop's within tolerance (the carry never went through a
    replicated host bounce that would have changed reduction order)."""
    ds, model = tiny_fed
    loo, scn = _run_both(
        model, ds, lambda: FedAvg(8, 3, 2, seed=0), mesh=mesh8,
        max_rounds=3, learning_rate=0.1, batch_size=16, seed=0, chunk=2,
    )
    for a, b in zip(jax.tree_util.tree_leaves(loo.final_params),
                    jax.tree_util.tree_leaves(scn.final_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# ---------------------------------------------------------------------------
# dispatch: fallbacks and rejections
# ---------------------------------------------------------------------------
def test_strategies_without_mesh_contract_fall_back_to_sharded_loop(tiny_fed):
    """Fedcom (update transform) and Dropout/TimelyFL (masks/freeze) keep
    supports_sharded_scan=False and silently run the sharded loop driver,
    reproducing it exactly."""
    ds, model = tiny_fed
    for mk in (lambda: Fedcom(8, 3, 1, seed=0, keep_frac=0.2),
               lambda: Dropout(8, 3, 1, seed=0, keep_rate=0.6),
               lambda: TimelyFL(8, 3, 1, seed=0)):
        assert not mk().supports_sharded_scan
        loo, scn = _run_both(
            model, ds, mk, max_rounds=2, learning_rate=0.1, batch_size=16,
            seed=0,
        )
        _assert_records_match(loo, scn)


def test_sharded_scan_rejects_wrongly_declared_support(tiny_fed):
    """A strategy that declares supports_sharded_scan but materializes masks
    or a transform is rejected at chunk build / dispatch, not silently
    miscomputed."""
    ds, model = tiny_fed

    class BadMask(Dropout):
        supports_sharded_scan = True

    with pytest.raises(ValueError, match="metadata-only|masks"):
        run_federated(model, ds, BadMask(8, 3, 1, seed=0, keep_rate=0.5),
                      engine="sharded", driver="scan", max_rounds=1,
                      learning_rate=0.1, batch_size=16, seed=0)

    class BadTransform(Fedcom):
        supports_sharded_scan = True

    with pytest.raises(ValueError, match="update_transform"):
        run_federated(model, ds, BadTransform(8, 3, 1, seed=0, keep_frac=0.2),
                      engine="sharded", driver="scan", max_rounds=1,
                      learning_rate=0.1, batch_size=16, seed=0)


def test_scan_still_rejects_sequential(tiny_fed):
    ds, model = tiny_fed
    with pytest.raises(ValueError, match="batched"):
        run_federated(model, ds, FedAvg(8, 3, 1, seed=0), max_rounds=1,
                      engine="sequential", driver="scan")


@needs8
def test_sharded_scan_full_participation_no_client_padding(tiny_fed, mesh8):
    """P == M == 8 divides the data axis: the no-client-padding branch (the
    index vector still must stay replicated) matches the sharded loop."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    mk = lambda: FLrce(8, 8, 1, dim=dim, es_threshold=50.0, seed=0)
    loo, scn = _run_both(
        model, ds, mk, mesh=mesh8, max_rounds=3, learning_rate=0.1,
        batch_size=16, seed=0, chunk=2,
    )
    for rec in scn.records:
        assert rec.selected == list(range(8))
    _assert_records_match(loo, scn)


def test_store_shard_matches_from_dataset_mesh(tiny_fed):
    """`from_dataset(mesh=...)` (one transfer) and `.shard()` (host bounce,
    for stores built without a mesh in hand) produce identical layouts."""
    from repro.data import DeviceClientStore
    from repro.launch.mesh import make_engine_mesh

    ds, _ = tiny_fed
    mesh = make_engine_mesh()
    a = DeviceClientStore.from_dataset(ds, mesh=mesh)
    b = DeviceClientStore.from_dataset(ds).shard(mesh)
    np.testing.assert_array_equal(np.asarray(a.x), np.asarray(b.x))
    np.testing.assert_array_equal(np.asarray(a.y), np.asarray(b.y))
    assert a.x.sharding == b.x.sharding
    assert a.num_clients == b.num_clients == 8


# ---------------------------------------------------------------------------
# image samples: the store keeps them flat, the chunk restores their shape
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cnn_fed():
    from repro.data import make_image_like
    from repro.models.cnn import PaperCNN

    ds = make_image_like(num_clients=6, num_samples=360, num_eval=60,
                         side=8, channels=1, num_classes=3, seed=0)
    model = PaperCNN(side=8, channels=1, num_classes=3, num_fc=2,
                     conv_channels=(4, 8), fc_width=16)
    return ds, model


def test_sharded_scan_image_samples_default_mesh(cnn_fed):
    """(1, 1) mesh: each step's batch is gathered from the flat store and
    reshaped to (B, H, W, C) inside the step scan."""
    ds, model = cnn_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    loo, scn = _run_both(
        model, ds, lambda: FLrce(6, 3, 1, dim=dim, es_threshold=2.0, seed=0),
        max_rounds=3, learning_rate=0.05, batch_size=16, seed=0, chunk=2,
    )
    _assert_records_match(loo, scn)


@needs8
def test_sharded_scan_image_samples_8dev(cnn_fed, mesh8):
    """(2, 4) mesh: the round's batches are gathered from the data-sharded
    flat store before the shard_mapped trainer, then reshaped per step."""
    ds, model = cnn_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    loo, scn = _run_both(
        model, ds, lambda: FLrce(6, 3, 1, dim=dim, es_threshold=2.0, seed=0),
        mesh=mesh8, max_rounds=3, learning_rate=0.05, batch_size=16, seed=0,
        chunk=2,
    )
    _assert_records_match(loo, scn)


_STORE_GATHER_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, re
import jax
from repro.data import make_federated_classification
from repro.fl import FLrce, run_federated, scan_driver
from repro.launch.mesh import make_debug_mesh
from repro.models.cnn import MLPClassifier, param_count

ds = make_federated_classification(num_clients=8, alpha=0.2, num_samples=800,
                                   num_eval=160, feature_dim=8, num_classes=3, seed=2)
model = MLPClassifier(feature_dim=8, num_classes=3, hidden=(16,))
dim = param_count(model.init(jax.random.PRNGKey(0)))
n_max = int(ds.client_sizes().max())
scan_driver._hlo_capture = hlo = []
run_federated(model, ds, FLrce(8, 3, 1, dim=dim, es_threshold=2.0, seed=0),
              engine="sharded", driver="scan", mesh=make_debug_mesh(2, 2),
              max_rounds=2, scan_chunk_rounds=2, learning_rate=0.1,
              batch_size=16, seed=0)
gathers = [l for l in hlo[0].splitlines() if re.search(r"all-gather(-start)?\(", l)]
whole = [l for l in gathers if re.search(rf"\[8,{n_max}(,\d+)?\]", l)]
print(json.dumps({"hlo": len(hlo), "store_gathers": whole}))
"""


def test_sharded_scan_never_gathers_the_whole_store_subprocess():
    """On a (2, 2) mesh the client store stays split over ``data``: the
    compiled chunk gathers the round's batches from it and never
    all-gathers the (M, N_max, …) store onto every device."""
    import json
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _STORE_GATHER_SCRIPT], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got == {"hlo": 1, "store_gathers": []}
