"""Pipelined chunk driver equivalence suite (DESIGN.md §1, pipelined chunks).

``pipeline=True`` (the scan driver's default) overlaps the next chunk's
build/H2D/dispatch with the current chunk's device execution and flushes the
current chunk's outputs while the next runs.  Both modes execute the SAME
jitted chunk program over the same schedule streams — pipelining only
reorders host work around the device timeline — so the equivalence bar here
is EXACT, not fp32-tolerant: records, ledger charges and the written-back
server state must be bitwise-identical between ``pipeline=True`` and
``pipeline=False``, including when an early stop cancels an in-flight
speculative chunk (the carried stop flag makes the post-stop chunk a masked
no-op whose outputs the host discards unread).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from equivalence import assert_runs_equivalent
from repro.data import make_federated_classification
from repro.fl import FLrce, run_federated
from repro.fl.baselines import Dropout, FedAvg, Fedprox, TimelyFL
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


def _run_pair(model, ds, make_strategy, *, chunk=3, engine="batched",
              mesh=None, **kw):
    """The same scan job serial (pipeline=False) and pipelined (True)."""
    mesh_kw = {"mesh": mesh} if mesh is not None else {}
    ser = run_federated(
        model, ds, make_strategy(), engine=engine, driver="scan",
        scan_chunk_rounds=chunk, pipeline=False, **mesh_kw, **kw,
    )
    pip = run_federated(
        model, ds, make_strategy(), engine=engine, driver="scan",
        scan_chunk_rounds=chunk, pipeline=True, **mesh_kw, **kw,
    )
    return ser, pip


def _assert_records_identical(ser, pip):
    """Bitwise record/ledger equality — same compiled program, same inputs,
    only the host's dispatch order differs (wall_s excepted)."""
    assert_runs_equivalent(ser, pip, bitwise=True)


def _strategies(dim):
    return {
        "fedavg": lambda: FedAvg(8, 3, 2, seed=0),
        "fedprox": lambda: Fedprox(8, 3, 2, seed=0, mu=0.01),
        "flrce": lambda: FLrce(8, 3, 2, dim=dim, es_threshold=2.0, seed=0),
    }


# ---------------------------------------------------------------------------
# pipelined ≡ serial across strategies × chunk alignments
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["fedavg", "fedprox", "flrce"])
@pytest.mark.parametrize("chunk", [1, 3, 5, 8])
def test_pipelined_matches_serial(tiny_fed, name, chunk):
    """Every chunk alignment (tail chunk, chunk > max_rounds, chunk=1 —
    which pipelines round pairs) reproduces the serial driver exactly."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    ser, pip = _run_pair(
        model, ds, _strategies(dim)[name], chunk=chunk,
        max_rounds=5, learning_rate=0.1, batch_size=16, seed=0,
    )
    _assert_records_identical(ser, pip)


def test_pipelined_matches_serial_variant_strategies(tiny_fed):
    """Host-selected strategies with per-round masks (Dropout) and freeze
    flags (TimelyFL): speculative chunk builds draw the SAME host RNG
    streams in the same order as the serial driver."""
    ds, model = tiny_fed
    for mk in (lambda: Dropout(8, 3, 1, seed=0, keep_rate=0.6),
               lambda: TimelyFL(8, 3, 1, seed=0)):
        ser, pip = _run_pair(
            model, ds, mk, chunk=2,
            max_rounds=4, learning_rate=0.1, batch_size=16, seed=0,
        )
        _assert_records_identical(ser, pip)


# ---------------------------------------------------------------------------
# mid-chunk early stop with a speculative chunk in flight
# ---------------------------------------------------------------------------
def test_pipelined_es_stop_cancels_speculative_chunk(tiny_fed):
    """FLrce stops mid-chunk while chunk k+1 is already dispatched: the
    cancelled chunk ran fully masked, its outputs are dropped unread, and
    records / ledger / stop round equal the serial driver bitwise."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    mk = lambda: FLrce(8, 3, 1, dim=dim, es_threshold=1e-6,
                       explore_decay=0.01, seed=0)
    ser, pip = _run_pair(
        model, ds, mk, chunk=4,
        max_rounds=40, learning_rate=0.8, batch_size=16, seed=0,
    )
    assert ser.stopped_early and pip.stopped_early
    assert pip.rounds_run < 40
    _assert_records_identical(ser, pip)
    assert pip.records[-1].stopped and pip.records[-1].evaluated
    # the stop really cancelled in-flight speculative work
    assert pip.driver_stats["cancelled_chunks"] >= 1
    assert ser.driver_stats["cancelled_chunks"] == 0


def test_pipelined_es_server_write_back_matches_serial(tiny_fed):
    """The deferred finalize (called once the carry is settled) writes back
    the same FLrceServer state the serial per-chunk finalize produces —
    Ω/H, PRNG, last_round, stop flag and stop round all bitwise equal."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    mk = lambda: FLrce(8, 3, 1, dim=dim, es_threshold=1e-6,
                       explore_decay=0.01, seed=0)
    ss, sp = mk(), mk()
    run_federated(model, ds, ss, max_rounds=40, learning_rate=0.8,
                  batch_size=16, seed=0, driver="scan", scan_chunk_rounds=4,
                  pipeline=False)
    run_federated(model, ds, sp, max_rounds=40, learning_rate=0.8,
                  batch_size=16, seed=0, driver="scan", scan_chunk_rounds=4,
                  pipeline=True)
    st_s, st_p = ss.server.state, sp.server.state
    assert st_s.t == st_p.t
    assert np.array_equal(np.asarray(ss.server._rng), np.asarray(sp.server._rng))
    np.testing.assert_array_equal(np.asarray(st_s.omega), np.asarray(st_p.omega))
    np.testing.assert_array_equal(
        np.asarray(st_s.heuristic), np.asarray(st_p.heuristic)
    )
    assert np.array_equal(np.asarray(st_s.last_round), np.asarray(st_p.last_round))
    assert st_s.stopped == st_p.stopped and st_s.stop_round == st_p.stop_round
    assert ss.last_round_was_exploit == sp.last_round_was_exploit


# ---------------------------------------------------------------------------
# eval_every interaction
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("eval_every", [2, 100])
def test_pipelined_eval_every(tiny_fed, eval_every):
    """The evaluation schedule (and the copied-forward accuracies of
    unevaluated rounds) survives pipelining unchanged."""
    ds, model = tiny_fed
    ser, pip = _run_pair(
        model, ds, lambda: FedAvg(8, 3, 1, seed=0), chunk=3,
        max_rounds=5, learning_rate=0.1, batch_size=16, seed=0,
        eval_every=eval_every,
    )
    _assert_records_identical(ser, pip)
    if eval_every == 100:
        assert [r.evaluated for r in pip.records] == [True] + [False] * 3 + [True]


# ---------------------------------------------------------------------------
# sharded chunks: the D-sharded donated carries alternate between the two
# in-flight programs
# ---------------------------------------------------------------------------
def test_sharded_pipelined_matches_serial_default_mesh(tiny_fed):
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    for mk in _strategies(dim).values():
        ser, pip = _run_pair(
            model, ds, mk, chunk=2, engine="sharded",
            max_rounds=4, learning_rate=0.1, batch_size=16, seed=0,
        )
        _assert_records_identical(ser, pip)


@needs8
@pytest.mark.parametrize("name", ["fedavg", "flrce"])
def test_sharded_pipelined_matches_serial_8dev(tiny_fed, mesh8, name):
    """Real (2, 4) mesh: D % 8 != 0 and P % data != 0 exercise the padding
    paths under double-buffered sharded schedule uploads."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    ser, pip = _run_pair(
        model, ds, _strategies(dim)[name], chunk=2, engine="sharded",
        mesh=mesh8, max_rounds=5, learning_rate=0.1, batch_size=16, seed=0,
    )
    _assert_records_identical(ser, pip)


@needs8
def test_sharded_pipelined_es_stop_8dev(tiny_fed, mesh8):
    """Mid-chunk stop on the real mesh with a speculative chunk in flight:
    the mesh-resident carry freezes, the cancelled chunk's D-sharded outputs
    are discarded, and the V map stays sharded after write-back."""
    ds, model = tiny_fed
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    mk = lambda: FLrce(8, 3, 1, dim=dim, es_threshold=1e-6,
                       explore_decay=0.01, seed=0)
    strat = mk()
    ser = run_federated(model, ds, mk(), engine="sharded", mesh=mesh8,
                        driver="scan", scan_chunk_rounds=4, pipeline=False,
                        max_rounds=40, learning_rate=0.8, batch_size=16, seed=0)
    pip = run_federated(model, ds, strat, engine="sharded", mesh=mesh8,
                        driver="scan", scan_chunk_rounds=4, pipeline=True,
                        max_rounds=40, learning_rate=0.8, batch_size=16, seed=0)
    assert ser.stopped_early and pip.stopped_early
    _assert_records_identical(ser, pip)
    assert pip.driver_stats["cancelled_chunks"] >= 1
    server = strat.server
    shards = server.state.updates.addressable_shards
    assert len({s.device for s in shards}) == 8


# ---------------------------------------------------------------------------
# knob validation + driver_stats contract
# ---------------------------------------------------------------------------
def test_pipeline_knob_requires_scan_driver(tiny_fed):
    ds, model = tiny_fed
    for pipeline in (True, False):
        with pytest.raises(ValueError, match="pipeline"):
            run_federated(model, ds, FedAvg(8, 3, 1, seed=0), max_rounds=1,
                          driver="loop", pipeline=pipeline)


def test_pipeline_defaults_on_for_scan(tiny_fed):
    ds, model = tiny_fed
    res = run_federated(model, ds, FedAvg(8, 3, 1, seed=0), driver="scan",
                        scan_chunk_rounds=2, max_rounds=4, learning_rate=0.1,
                        batch_size=16, seed=0)
    assert res.driver_stats["pipeline"] is True


def test_driver_stats_contract(tiny_fed):
    """driver_stats counts chunks/speculation and partitions wall time; a
    multi-chunk pipelined run really dispatched ahead, the loop driver
    reports no stats."""
    ds, model = tiny_fed
    ser, pip = _run_pair(
        model, ds, lambda: FedAvg(8, 3, 1, seed=0), chunk=2,
        max_rounds=6, learning_rate=0.1, batch_size=16, seed=0,
    )
    for res, pipelined in ((ser, False), (pip, True)):
        st = res.driver_stats
        assert st["driver"] == "scan" and st["pipeline"] is pipelined
        assert st["chunks"] == 3
        assert st["total_s"] > 0
        assert st["host_build_s"] >= 0 and st["device_wait_s"] >= 0
        assert st["host_flush_s"] >= 0
        # the first chunk's dispatch compiles (FedAvg's host-drawn cohorts
        # may land in a second candidate bucket); the compiles, the chunk's
        # and the build's eager ops', are reported on their own, and the
        # four phase spans are disjoint parts of the loop
        assert st["compiles_chunk"] >= 1 and st["dispatch_s"] > 0
        assert st["compiles_total"] >= st["compiles_chunk"]
        assert 0 < st["compile_s"] <= st["total_s"]
        assert (st["host_build_s"] + st["dispatch_s"] + st["device_wait_s"]
                + st["host_flush_s"]) <= st["total_s"]
        assert st["setup_store_s"] > 0
    assert ser.driver_stats["speculative_chunks"] == 0
    # depth-2 pipeline: every chunk after the first was dispatched while its
    # predecessor was still in flight
    assert pip.driver_stats["speculative_chunks"] == 2
    assert pip.driver_stats["cancelled_chunks"] == 0
    loop = run_federated(model, ds, FedAvg(8, 3, 1, seed=0), max_rounds=1,
                         learning_rate=0.1, batch_size=16, seed=0)
    assert loop.driver_stats == {}
