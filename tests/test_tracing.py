"""The scan driver's spans, phase scopes and step counters.

Host spans: every chunk phase of ``run_scan_driver`` is a profiler
``TraceAnnotation`` named ``fl.<phase>`` with the chunk's first round in its
``chunk`` stat, recorded on the host's python thread line of the trace, and
the same span's seconds go into ``driver_stats``.  Phase scopes: the
compiled chunk's ops carry ``fl/<phase>`` in their ``op_name`` metadata
(the device op's ``tf_op`` in a profiler trace).  Step counters:
``steps_useful`` is the real local steps of the flushed rounds' cohorts,
``steps_executed`` the padded cohort × trip count (the cohort's last real
step) the rounds ran, ``steps_bucket`` the rounds × padded cohort × step
bucket.
"""
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.analysis.compile_guard import CompileCounter
from repro.data import make_federated_classification
from repro.fl import AsyncConfig, FLrce, run_federated, scan_driver
from repro.fl.baselines import QuantizedFL
from repro.models.cnn import MLPClassifier, param_count

CHUNK_SPANS = (
    "fl.build", "fl.build.candidates", "fl.build.configs", "fl.build.schedule",
    "fl.build.variants", "fl.build.place", "fl.dispatch", "fl.wait", "fl.flush",
)
JOB = dict(max_rounds=4, scan_chunk_rounds=2, learning_rate=0.1,
           batch_size=16, seed=0)


@pytest.fixture(scope="module")
def tiny_fed():
    ds = make_federated_classification(
        num_clients=8, alpha=0.2, num_samples=800, num_eval=160,
        feature_dim=8, num_classes=3, seed=2,
    )
    model = MLPClassifier(feature_dim=8, num_classes=3, hidden=(16,))
    return ds, model, param_count(model.init(jax.random.PRNGKey(0)))


def _host_spans(trace_dir):
    """(name, chunk, start_ns, end_ns) of every ``fl.`` event on the host's
    python thread lines."""
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    assert len(path) == 1, path
    out = []
    for plane in ProfileData.from_file(path[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if not line.name.startswith("python"):
                continue
            for ev in line.events:
                if ev.name.startswith("fl."):
                    chunk = dict(ev.stats).get("chunk")
                    out.append((ev.name, chunk, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


@pytest.fixture(scope="module", params=["pipelined", "serial"])
def traced_job(request, tiny_fed, tmp_path_factory):
    """A two-chunk FLrce job run under the profiler, with the host-built
    schedules recorded as the driver builds them."""
    ds, model, dim = tiny_fed
    built = []
    real = scan_driver.build_chunk_schedule

    def recording(sizes, epochs, batch_size, t0, rng_for, **kw):
        sched = real(sizes, epochs, batch_size, t0, rng_for, **kw)
        built.append((t0, np.asarray(kw["client_ids"]), sched))
        return sched

    trace_dir = str(tmp_path_factory.mktemp(f"trace_{request.param}"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(scan_driver, "build_chunk_schedule", recording)
        jax.profiler.start_trace(trace_dir)
        try:
            res = run_federated(
                model, ds, FLrce(8, 3, 1, dim=dim, es_threshold=2.0, seed=0),
                driver="scan", pipeline=request.param == "pipelined", **JOB,
            )
        finally:
            jax.profiler.stop_trace()
    return res, _host_spans(trace_dir), built


def test_each_span_once_per_chunk(traced_job):
    res, spans, _ = traced_job
    assert res.driver_stats["chunks"] == 2
    for name in CHUNK_SPANS:
        assert sorted(c for n, c, _, _ in spans if n == name) == [0, 2], name
    # the job's set-up and loop spans, once each
    for name in ("fl.setup.store", "fl.job"):
        assert [c for n, c, _, _ in spans if n == name] == [None], name


def test_build_phases_nest_in_their_build(traced_job):
    _, spans, _ = traced_job
    builds = {c: (s, e) for n, c, s, e in spans if n == "fl.build"}
    phases = [(n, c, s, e) for n, c, s, e in spans if n.startswith("fl.build.")]
    assert len(phases) == 2 * 5
    for name, chunk, s, e in phases:
        b0, b1 = builds[chunk]
        assert b0 <= s <= e <= b1, (name, chunk)


def test_chunk_phases_in_order(traced_job):
    """Per chunk: build, then dispatch, then the wait on it, then its
    flush; the spans of one chunk never overlap."""
    _, spans, _ = traced_job
    for chunk in (0, 2):
        at = {n: (s, e) for n, c, s, e in spans if c == chunk}
        order = ["fl.build", "fl.dispatch", "fl.wait", "fl.flush"]
        for a, b in zip(order, order[1:]):
            assert at[a][1] <= at[b][0], (chunk, a, b)


def test_step_counters(traced_job):
    """``steps_useful`` is the sum of ``step_valid`` over the selected rows
    of the host-built schedules; ``steps_executed`` is Σ P_pad × the
    largest real step count among a round's selected rows, and
    ``steps_bucket`` rounds × P_pad × S."""
    res, _, built = traced_job
    st = res.driver_stats
    by_t0 = {t0: (cols, sched) for t0, cols, sched in built}
    useful = executed = bucket = 0
    for rec in res.records:
        t0 = rec.t - rec.t % JOB["scan_chunk_rounds"]
        cols, sched = by_t0[t0]
        slots = np.searchsorted(cols, rec.selected)
        rows = sched.step_valid[rec.t - t0, slots]
        useful += int(rows.sum())
        executed += 3 * int(rows.sum(axis=1).max())
        bucket += 3 * sched.num_steps
    assert st["steps_useful"] == useful > 0
    assert st["steps_executed"] == executed
    assert st["steps_bucket"] == bucket
    assert st["steps_useful"] <= st["steps_executed"] <= st["steps_bucket"]


def _scopes(hlo_text):
    return set(re.findall(r'op_name="[^"]*?(fl/[a-z_]+)', hlo_text))


BODY = {"fl/select", "fl/train", "fl/aggregate", "fl/round_stats", "fl/eval"}


@pytest.mark.parametrize("case", ["flrce", "flrce_async", "flrce_sharded", "quantized"])
def test_chunk_hlo_carries_phase_scopes(tiny_fed, monkeypatch, case):
    """The compiled chunk's ops name the phase they belong to: the scan
    body's scopes, FLrce's ingest and Alg. 3 (sync and async forms), and
    the update transform where the strategy has one."""
    ds, model, dim = tiny_fed
    kw = dict(JOB)
    if case == "quantized":
        strategy, want = QuantizedFL(8, 3, 1, seed=0), BODY | {"fl/transform"}
    else:
        strategy = FLrce(8, 3, 1, dim=dim, es_threshold=2.0, seed=0)
        want = BODY | {"fl/ingest", "fl/early_stop"}
    if case == "flrce_async":
        kw["async_rounds"] = AsyncConfig(max_staleness=1)
    if case == "flrce_sharded":
        kw["engine"] = "sharded"
    monkeypatch.setattr(scan_driver, "_hlo_capture", [])
    run_federated(model, ds, strategy, driver="scan", **kw)
    assert len(scan_driver._hlo_capture) == 1
    assert _scopes(scan_driver._hlo_capture[0]) == want


_RESHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
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
scan_driver._hlo_capture = hlo = []
run_federated(model, ds, FLrce(8, 3, 1, dim=dim, es_threshold=2.0, seed=0),
              engine="sharded", driver="scan", mesh=make_debug_mesh(2, 1),
              max_rounds=2, scan_chunk_rounds=2, learning_rate=0.1,
              batch_size=16, seed=0)
print(json.dumps(sorted(set(re.findall(r'op_name="[^"]*?(fl/[a-z_]+)', hlo[0])))))
"""


def test_sharded_chunk_hlo_carries_reshard_scope_subprocess():
    """On a two-device ``data`` mesh the cohort is padded (P=3 to 4) and
    resharded to the D-sharded layout: those ops carry ``fl/reshard``."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _RESHARD_SCRIPT], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    got = set(json.loads(out.stdout.strip().splitlines()[-1]))
    assert got == BODY | {"fl/reshard", "fl/ingest", "fl/early_stop"}


def test_compile_counter_sums_compile_seconds():
    """A fresh program's compile adds its seconds; an in-memory cache hit
    adds neither a compile nor a second."""
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + x.sum())
    with CompileCounter() as cc:
        f(jnp.ones((7, 5))).block_until_ready()
    assert cc.compiles >= 1 and cc.seconds > 0.0
    with CompileCounter() as again:
        f(jnp.ones((7, 5))).block_until_ready()
    assert again.compiles == 0 and again.seconds == 0.0
