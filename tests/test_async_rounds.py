"""Staleness-aware async rounds (``async_rounds=AsyncConfig``) suite.

The equivalence spine: with ``max_staleness=0`` every update lands in its
departure round with weight exactly 1.0, so the async chunk program must
reproduce the synchronous pipelined driver BITWISE — records, ledger and the
written-back FLrce server state — across strategies, pipeline on/off, and
single-device vs the (2, 4) mesh.  With ``max_staleness > 0`` the run is a
different experiment; what stays invariant is the resource accounting
(charges are departure-based, so energy/bytes equal the synchronous run's)
and conservation (every departure either arrived or is pending at exit).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from equivalence import assert_runs_equivalent
from repro.core.heuristics import heuristic_from_omega
from repro.data import make_federated_classification
from repro.fl import AsyncConfig, FLrce, run_federated, staleness_of
from repro.fl.async_rounds import AsyncPlan, synthetic_delays
from repro.fl.baselines import Dropout, FedAvg, Fedprox, PyramidFL
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
def tiny_fed():
    ds = make_federated_classification(
        num_clients=8, alpha=0.2, num_samples=800, num_eval=160,
        feature_dim=8, num_classes=3, seed=2,
    )
    return ds, MLPClassifier(feature_dim=8, num_classes=3, hidden=(16,))


@pytest.fixture(scope="module")
def mesh8():
    return make_debug_mesh(2, 4)


def _strategies(dim):
    return {
        "fedavg": lambda: FedAvg(8, 3, 2, seed=0),
        "fedprox": lambda: Fedprox(8, 3, 2, seed=0, mu=0.01),
        "flrce": lambda: FLrce(8, 3, 2, dim=dim, es_threshold=2.0, seed=0),
    }


def _run_pair(model, ds, make_strategy, *, async_cfg, chunk=2, engine="batched",
              mesh=None, **kw):
    """The same scan job synchronous and with ``async_rounds=async_cfg``."""
    mesh_kw = {"mesh": mesh} if mesh is not None else {}
    kw.setdefault("max_rounds", 5)
    kw.setdefault("learning_rate", 0.1)
    kw.setdefault("batch_size", 16)
    kw.setdefault("seed", 0)
    sync = run_federated(
        model, ds, make_strategy(), engine=engine, driver="scan",
        scan_chunk_rounds=chunk, **mesh_kw, **kw,
    )
    asy = run_federated(
        model, ds, make_strategy(), engine=engine, driver="scan",
        scan_chunk_rounds=chunk, async_rounds=async_cfg, **mesh_kw, **kw,
    )
    return sync, asy


# ---------------------------------------------------------------------------
# max_staleness=0 ≡ synchronous, bitwise
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["fedavg", "fedprox", "flrce"])
@pytest.mark.parametrize("pipeline", [True, False])
def test_async_s0_matches_sync(tiny_fed, name, pipeline):
    """τ=0 everywhere: the arrival buffer holds each cohort for exactly zero
    rounds and the staleness-weighted Eq. 4 multiplies by exactly 1.0 — same
    floats, same records, same ledger, same final params."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    sync, asy = _run_pair(
        model, ds, _strategies(dim)[name],
        async_cfg=AsyncConfig(max_staleness=0), pipeline=pipeline,
    )
    assert_runs_equivalent(sync, asy, bitwise=True)
    assert asy.driver_stats["async_max_staleness"] == 0
    assert asy.driver_stats["async_pending_at_exit"] == 0


def test_async_s0_server_write_back_matches_sync(tiny_fed):
    """FLrce's deferred finalize writes back the same server state the
    synchronous driver produces: H, V/A maps, last_round and host PRNG
    bitwise, Ω to one ulp of 1.0 (its entries are cosines in [-1, 1]).

    At τ=0 the async ingest computes the sync ingest's formulas, but its
    update rows are read back out of the arrival ring (a
    dynamic_update_slice of the round's (P, D) buffer), and XLA fuses the
    Eq. 5/6 dots downstream of that differently from the sync program's,
    which reads the buffer directly.  The dots then sum in another order,
    and a few Ω entries land 2**-23 apart."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    mk = lambda: FLrce(8, 3, 2, dim=dim, es_threshold=2.0, seed=0)
    ss, sa = mk(), mk()
    kw = dict(max_rounds=5, learning_rate=0.1, batch_size=16, seed=0,
              driver="scan", scan_chunk_rounds=2)
    run_federated(model, ds, ss, **kw)
    run_federated(model, ds, sa, async_rounds=AsyncConfig(max_staleness=0), **kw)
    st_s, st_a = ss.server.state, sa.server.state
    assert st_s.t == st_a.t
    assert np.array_equal(np.asarray(ss.server._rng), np.asarray(sa.server._rng))
    np.testing.assert_allclose(
        np.asarray(st_s.omega), np.asarray(st_a.omega),
        rtol=0, atol=np.finfo(np.float32).eps,
    )
    # H sums Ω's rows, so it inherits Ω's last-bit gap: each run's H is
    # held bitwise to the row sums of its own Ω instead
    for st in (st_s, st_a):
        np.testing.assert_array_equal(
            np.asarray(st.heuristic), np.asarray(heuristic_from_omega(st.omega))
        )
    for field in ("updates", "anchors", "last_round"):
        np.testing.assert_array_equal(
            np.asarray(getattr(st_s, field)), np.asarray(getattr(st_a, field)),
            err_msg=field,
        )
    assert st_s.stopped == st_a.stopped and st_s.stop_round == st_a.stop_round


def test_async_s0_early_stop_matches_sync(tiny_fed):
    """Alg. 3 fires mid-chunk: the async driver's masked-conflict-pair count
    over an all-arrived buffer equals the sync pair count, so the stop lands
    on the same round and cancels in-flight work identically."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    mk = lambda: FLrce(8, 3, 1, dim=dim, es_threshold=1e-6,
                       explore_decay=0.01, seed=0)
    sync, asy = _run_pair(
        model, ds, mk, async_cfg=AsyncConfig(max_staleness=0), chunk=4,
        max_rounds=40, learning_rate=0.8,
    )
    assert sync.stopped_early and asy.stopped_early
    assert asy.rounds_run < 40
    assert_runs_equivalent(sync, asy, bitwise=True)


@needs8
@pytest.mark.parametrize("name", ["fedavg", "flrce"])
def test_async_s0_matches_sync_8dev(tiny_fed, mesh8, name):
    """Real (2, 4) mesh: the D-sharded arrival buffer and the sharded
    staleness-weighted aggregation reproduce the sync sharded chunks."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    sync, asy = _run_pair(
        model, ds, _strategies(dim)[name], engine="sharded", mesh=mesh8,
        async_cfg=AsyncConfig(max_staleness=0),
    )
    assert_runs_equivalent(sync, asy, bitwise=True)


# ---------------------------------------------------------------------------
# max_staleness > 0: conservation + departure-based accounting
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["fedavg", "flrce"])
def test_async_staleness_accounting(tiny_fed, name):
    """Delayed delivery changes the trajectory but not the resource story:
    charges are departure-based, so the async ledger's energy/bytes equal the
    synchronous run's, and every departed update is either recorded in the
    arrival histogram or pending at exit."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    sync, asy = _run_pair(
        model, ds, _strategies(dim)[name],
        async_cfg=AsyncConfig(max_staleness=2), chunk=2, max_rounds=6,
    )
    st = asy.driver_stats
    assert st["async_max_staleness"] == 2
    departures = sum(len(r.selected) for r in asy.records)
    assert st["async_arrivals"] + st["async_pending_at_exit"] == departures
    hist = asy.ledger.arrivals_by_staleness
    assert sum(hist.values()) == st["async_arrivals"]
    assert all(0 <= tau <= 2 for tau in hist)
    if name == "flrce":
        # device-side selection fixes the candidate shapes: with aligned
        # chunks the async program compiled exactly once (recompile sentinel)
        assert st["compiles_chunk"] == 1
    else:
        # host-selected cohorts pow2-bucket the candidate axis: one compile
        # per bucket, never per chunk
        assert 1 <= st["compiles_chunk"] <= 2
    # departure-based charging: same cohorts trained and uploaded, so the
    # energy/bandwidth totals are the synchronous run's exactly
    assert asy.ledger.energy_j == sync.ledger.energy_j
    assert asy.ledger.bytes_up == sync.ledger.bytes_up
    assert asy.ledger.bytes_down == sync.ledger.bytes_down


def test_async_synthetic_trace_actually_delays(tiny_fed):
    """The seeded synthetic trace is not degenerate: with max_staleness=2
    some arrivals land late (τ > 0) — otherwise the async path silently
    collapses to sync and tests above prove nothing."""
    ds, model = tiny_fed
    _, asy = _run_pair(
        model, ds, lambda: FedAvg(8, 3, 1, seed=0),
        async_cfg=AsyncConfig(max_staleness=2), max_rounds=6,
    )
    hist = asy.ledger.arrivals_by_staleness
    assert any(tau > 0 for tau, n in hist.items() if n > 0)


def test_async_zero_delay_trace_matches_sync_bitwise(tiny_fed):
    """A per-client delay profile of all zeros is the synchronous schedule
    even at max_staleness > 0: the τ=0 column of the decay table is 1.0 and
    the wider ring buffer never holds anything back."""
    ds, model = tiny_fed
    sync, asy = _run_pair(
        model, ds, lambda: FedAvg(8, 3, 1, seed=0),
        async_cfg=AsyncConfig(max_staleness=2, trace=np.zeros(8, np.int64)),
    )
    assert_runs_equivalent(sync, asy, bitwise=True)
    assert list(asy.ledger.arrivals_by_staleness) == [0]


def test_async_per_client_trace_profile(tiny_fed):
    """A heterogeneous per-client profile (stragglers at fixed delays) is
    honored: observed staleness histogram only contains delays the profile
    assigns, and conservation holds."""
    ds, model = tiny_fed
    trace = np.asarray([0, 0, 1, 0, 2, 0, 1, 0], np.int64)
    _, asy = _run_pair(
        model, ds, lambda: FedAvg(8, 3, 1, seed=0),
        async_cfg=AsyncConfig(max_staleness=2, trace=trace), max_rounds=6,
    )
    st = asy.driver_stats
    departures = sum(len(r.selected) for r in asy.records)
    assert st["async_arrivals"] + st["async_pending_at_exit"] == departures
    assert set(asy.ledger.arrivals_by_staleness) <= {0, 1, 2}


def test_async_plan_delays_respect_trace_clipping():
    """Out-of-range trace values clip to [0, max_staleness] at resolve time
    and at gather time — a hostile profile cannot index past the ring."""
    from repro.fl.async_rounds import resolve_async_plan

    cfg = AsyncConfig(max_staleness=1, trace=np.asarray([5, 0, -3, 1]))
    plan = resolve_async_plan(cfg, num_clients=4, seed=0, put=jnp.asarray)
    taus = np.asarray(plan.delays(3, jnp.asarray([0, 1, 2, 3])))
    assert taus.tolist() == [1, 0, 0, 1]


def test_synthetic_delays_deterministic_and_bounded():
    ids = jnp.arange(32)
    a = np.asarray(synthetic_delays(7, 11, ids, 3))
    b = np.asarray(synthetic_delays(7, 11, ids, 3))
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 0 and a.max() <= 3
    # different rounds / seeds decorrelate
    c = np.asarray(synthetic_delays(7, 12, ids, 3))
    d = np.asarray(synthetic_delays(8, 11, ids, 3))
    assert not np.array_equal(a, c) or not np.array_equal(a, d)
    assert np.asarray(synthetic_delays(7, 11, ids, 0)).max() == 0


def test_staleness_of_convention():
    assert staleness_of(3, 5) == 2
    np.testing.assert_array_equal(
        np.asarray(staleness_of(jnp.asarray([3, 4]), 5)), [2, 1]
    )


# ---------------------------------------------------------------------------
# validation: every misuse is a loud error, never a silent sync run
# ---------------------------------------------------------------------------
def test_async_requires_scan_driver(tiny_fed):
    ds, model = tiny_fed
    with pytest.raises(ValueError, match="scan"):
        run_federated(model, ds, FedAvg(8, 3, 1, seed=0), max_rounds=1,
                      driver="loop", async_rounds=AsyncConfig(max_staleness=1))


def test_async_rejects_non_config(tiny_fed):
    ds, model = tiny_fed
    with pytest.raises(ValueError, match="AsyncConfig"):
        run_federated(model, ds, FedAvg(8, 3, 1, seed=0), max_rounds=1,
                      driver="scan", async_rounds=2)


def test_async_rejects_unsupported_strategy(tiny_fed):
    ds, model = tiny_fed
    assert not getattr(Dropout, "supports_async", False)
    with pytest.raises(ValueError, match="supports_async"):
        run_federated(model, ds, Dropout(8, 3, 1, seed=0, keep_rate=0.6),
                      max_rounds=1, driver="scan",
                      async_rounds=AsyncConfig(max_staleness=1))


def test_async_rejects_loop_fallback(tiny_fed):
    """A strategy that claims async support but cannot compile must error,
    not silently run the synchronous loop driver as a fake experiment."""
    ds, model = tiny_fed

    class NoScanFedAvg(FedAvg):
        supports_scan = False
        supports_async = True

    with pytest.raises(ValueError, match="loop driver"):
        run_federated(model, ds, NoScanFedAvg(8, 3, 1, seed=0), max_rounds=1,
                      driver="scan", async_rounds=AsyncConfig(max_staleness=1))
    assert not getattr(PyramidFL, "supports_async", False)


def test_async_rejects_paged_store(tiny_fed):
    ds, model = tiny_fed
    with pytest.raises(ValueError, match="resident"):
        run_federated(model, ds, FedAvg(8, 3, 1, seed=0), max_rounds=1,
                      driver="scan", client_store="paged",
                      async_rounds=AsyncConfig(max_staleness=1))


def test_async_rejects_sketched_flrce(tiny_fed):
    """Sketched V/A maps (va_rows=K) withhold post_round_async: the LRU row
    reassignment cannot ingest out-of-order arrivals, and the driver refuses
    rather than dropping FLrce's bookkeeping."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    strat = FLrce(8, 3, 1, dim=dim, es_threshold=1e9, seed=0, va_rows=4)
    with pytest.raises(ValueError, match="post_round_async"):
        run_federated(model, ds, strat, max_rounds=1, driver="scan",
                      learning_rate=0.1, batch_size=16, seed=0,
                      async_rounds=AsyncConfig(max_staleness=1))


def test_async_config_validation():
    with pytest.raises(ValueError, match="max_staleness"):
        AsyncConfig(max_staleness=-1).validate()
    with pytest.raises(ValueError, match="max_staleness"):
        AsyncConfig(max_staleness=1.5).validate()
    with pytest.raises(ValueError, match="decay"):
        AsyncConfig(max_staleness=1, decay=lambda t: 0.9 ** (t + 1)).validate()
    with pytest.raises(ValueError, match="finite"):
        AsyncConfig(max_staleness=2,
                    decay=lambda t: [1.0, float("inf"), 0.5][t]).validate()
    with pytest.raises(ValueError, match="1-D"):
        AsyncConfig(max_staleness=1, trace=np.zeros((2, 2))).validate()
    with pytest.raises(ValueError, match="clients"):
        AsyncConfig(max_staleness=1, trace=np.zeros(3)).validate(num_clients=8)
    # the good cases validate clean
    AsyncConfig(max_staleness=0).validate(num_clients=8)
    AsyncConfig(max_staleness=3, decay=lambda t: 1.0 / (1 + t * t),
                trace=np.zeros(8)).validate(num_clients=8)
