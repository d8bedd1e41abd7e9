"""flcheck (repro.analysis): per-rule TP/TN fixtures, suppression, CLI,
self-application, the compile-count sentinel, and the docs sync contract.

Each rule gets (at least) one true-positive snippet that must fire, one
true-negative that must stay silent, and a disable-comment fixture proving
the escape hatch works.  The self-application test is the real acceptance
criterion: ``python -m repro.analysis src/ benchmarks/`` exits 0 — the
repo obeys its own invariants.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import RULES, lint_text, render_rule_table
from repro.analysis.compile_guard import CompileCounter, assert_compiles
from repro.analysis.conformance import ConformancePass
from repro.analysis.runner import (
    DOC_BEGIN_MARKER,
    DOC_END_MARKER,
    iter_python_files,
)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def rule_ids(src: str, path: str = "fixture.py", select=None):
    return [f.rule_id for f in lint_text(textwrap.dedent(src), path, select=select)]


# ---------------------------------------------------------------------------
# FLC001 donation-discipline
# ---------------------------------------------------------------------------
def test_flc001_cand_page_param_in_donated_position_fires():
    src = """
    import jax

    def chunk(w, cand_dev, xs):
        return w

    run = jax.jit(chunk, donate_argnums=(0, 1))
    """
    assert rule_ids(src, select=["FLC001"]) == ["FLC001"]


def test_flc001_use_after_donate_fires():
    src = """
    import jax

    step = jax.jit(update, donate_argnums=(0,))

    def drive(w, xs):
        out = step(w, xs)
        return w.sum()
    """
    assert rule_ids(src, select=["FLC001"]) == ["FLC001"]


def test_flc001_decorated_partial_jit_fires():
    src = """
    import jax
    from functools import partial

    @partial(jax.jit, donate_argnums=(0,))
    def step(w, xs):
        return w

    def drive(w, xs):
        step(w, xs)
        return w + 1
    """
    assert rule_ids(src, select=["FLC001"]) == ["FLC001"]


def test_flc001_carry_rebind_is_clean():
    src = """
    import jax

    step = jax.jit(update, donate_argnums=(0,))

    def drive(w, xs):
        for x in xs:
            w = step(w, x)
        return w
    """
    assert rule_ids(src, select=["FLC001"]) == []


def test_flc001_disable_comment_suppresses():
    src = """
    import jax

    def chunk(w, page_x):
        return w

    run = jax.jit(chunk, donate_argnums=(1,))  # flcheck: disable=FLC001
    """
    assert rule_ids(src, select=["FLC001"]) == []


# ---------------------------------------------------------------------------
# FLC002 host-sync-hot-path
# ---------------------------------------------------------------------------
def test_flc002_host_sync_in_scan_body_fires():
    src = """
    import jax, numpy as np
    from jax import lax

    def body(carry, x):
        loss = float(carry.sum())
        host = np.asarray(x)
        return carry, x.item()

    out = lax.scan(body, init, xs)
    """
    assert sorted(rule_ids(src, select=["FLC002"])) == ["FLC002"] * 3


def test_flc002_sync_outside_scan_body_is_clean():
    src = """
    import jax, numpy as np

    def flush(outs):
        return jax.device_get(outs)
    """
    assert rule_ids(src, select=["FLC002"]) == []


def test_flc002_dispatch_scope_only_checked_in_scan_driver():
    src = """
    import jax

    def run_chunk(self, plan):
        jax.block_until_ready(plan)
        return plan
    """
    assert rule_ids(src, "src/repro/fl/scan_driver.py",
                    select=["FLC002"]) == ["FLC002"]
    # same code in any other module: host Python, not the dispatch path
    assert rule_ids(src, "src/repro/fl/other.py", select=["FLC002"]) == []


def test_flc002_np_asarray_allowed_in_dispatch_scope():
    src = """
    import numpy as np

    def build_chunk(t0):
        return np.asarray([t0])
    """
    assert rule_ids(src, "src/repro/fl/scan_driver.py", select=["FLC002"]) == []


def test_flc002_disable_comment_suppresses():
    src = """
    from jax import lax

    def body(carry, x):
        v = float(x)  # flcheck: disable=FLC002
        return carry, v

    out = lax.scan(body, init, xs)
    """
    assert rule_ids(src, select=["FLC002"]) == []


# ---------------------------------------------------------------------------
# FLC003 sharding-pin
# ---------------------------------------------------------------------------
_MESH_PREAMBLE = """
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding
"""


def test_flc003_unpinned_concat_index_reaching_gather_fires():
    src = _MESH_PREAMBLE + """
def body(carry, x):
    ids = jnp.concatenate([x, x])
    rows = table[ids]
    return carry, rows

out = lax.scan(body, init, xs)
"""
    assert rule_ids(src, select=["FLC003"]) == ["FLC003"]


def test_flc003_pinned_index_is_clean():
    src = _MESH_PREAMBLE + """
def body(carry, x):
    ids = jnp.concatenate([x, x])
    ids = jax.lax.with_sharding_constraint(ids, rep)
    rows = table[ids]
    return carry, rows

out = lax.scan(body, init, xs)
"""
    assert rule_ids(src, select=["FLC003"]) == []


def test_flc003_silent_without_mesh_markers():
    # single-device module: same pattern, no layout hazard, no finding
    src = """
    import jax.numpy as jnp
    from jax import lax

    def body(carry, x):
        ids = jnp.concatenate([x, x])
        rows = table[ids]
        return carry, rows

    out = lax.scan(body, init, xs)
    """
    assert rule_ids(src, select=["FLC003"]) == []


def test_flc003_disable_comment_suppresses():
    src = _MESH_PREAMBLE + """
def body(carry, x):
    ids = jnp.unique(x, size=4)
    rows = table[ids]  # flcheck: disable=FLC003
    return carry, rows

out = lax.scan(body, init, xs)
"""
    assert rule_ids(src, select=["FLC003"]) == []


# ---------------------------------------------------------------------------
# FLC004 rng-discipline
# ---------------------------------------------------------------------------
def test_flc004_split_and_reuse_fires():
    src = """
    import jax

    def draw(key, shape):
        a, b = jax.random.split(key)
        return jax.random.normal(key, shape)
    """
    assert rule_ids(src, select=["FLC004"]) == ["FLC004"]


def test_flc004_same_key_double_draw_fires():
    src = """
    import jax

    def draw(key, shape):
        x = jax.random.normal(key, shape)
        y = jax.random.uniform(key, shape)
        return x, y
    """
    assert rule_ids(src, select=["FLC004"]) == ["FLC004"]


def test_flc004_rebound_split_chain_is_clean():
    src = """
    import jax

    def draw(key, shape):
        key, sub = jax.random.split(key)
        x = jax.random.normal(sub, shape)
        key, sub = jax.random.split(key)
        y = jax.random.uniform(sub, shape)
        return x, y
    """
    assert rule_ids(src, select=["FLC004"]) == []


def test_flc004_fold_in_streams_are_clean():
    src = """
    import jax

    def client_rng(seed_key, t, cid):
        k = jax.random.fold_in(seed_key, t)
        k = jax.random.fold_in(k, cid)
        return jax.random.permutation(k, 10)
    """
    assert rule_ids(src, select=["FLC004"]) == []


def test_flc004_numpy_stateful_api_excluded():
    src = """
    import numpy as np

    def draw(seed):
        rng = np.random.default_rng(seed)
        a = np.random.default_rng(seed)
        return rng, a
    """
    assert rule_ids(src, select=["FLC004"]) == []


def test_flc004_disable_comment_suppresses():
    src = """
    import jax

    def draw(key, shape):
        a, b = jax.random.split(key)
        return jax.random.normal(key, shape)  # flcheck: disable=FLC004
    """
    assert rule_ids(src, select=["FLC004"]) == []


# ---------------------------------------------------------------------------
# FLC005 wall-clock
# ---------------------------------------------------------------------------
def test_flc005_time_time_fires():
    src = """
    import time

    def bench(fn):
        t0 = time.time()
        fn()
        return time.time() - t0
    """
    assert rule_ids(src, select=["FLC005"]) == ["FLC005", "FLC005"]


def test_flc005_perf_counter_is_clean():
    src = """
    import time

    def bench(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0
    """
    assert rule_ids(src, select=["FLC005"]) == []


def test_flc005_disable_for_genuine_timestamp():
    src = """
    import time

    stamp = time.time()  # flcheck: disable=FLC005
    """
    assert rule_ids(src, select=["FLC005"]) == []


# ---------------------------------------------------------------------------
# FLC006 strategy-conformance (cross-file class table, reported at finalize)
# ---------------------------------------------------------------------------
_ROOT = """
class Strategy:
    supports_scan = False
    supports_sharded_scan = False
    supports_paged_store = True
"""


def test_flc006_sharded_scan_without_scan_fires():
    src = _ROOT + """
class Bad(Strategy):
    supports_sharded_scan = True
"""
    assert rule_ids(src, select=["FLC006"]) == ["FLC006"]


def test_flc006_sharded_scan_with_update_transform_fires():
    src = _ROOT + """
class Bad(Strategy):
    supports_scan = True
    supports_sharded_scan = True

    def update_transform(self, template):
        return None
"""
    assert rule_ids(src, select=["FLC006"]) == ["FLC006"]


def test_flc006_scan_post_round_without_scan_program_fires():
    src = _ROOT + """
class Bad(Strategy):
    supports_scan = True

    def post_round(self, t, w, ids, u, stats):
        return False
"""
    assert rule_ids(src, select=["FLC006"]) == ["FLC006"]


def test_flc006_removed_hook_fires():
    src = _ROOT + """
class Ancient(Strategy):
    def process_update(self, u):
        return u
"""
    assert rule_ids(src, select=["FLC006"]) == ["FLC006"]


def test_flc006_opt_out_without_fallback_reason_fires():
    src = _ROOT + """
class LoopOnly(Strategy):
    supports_scan = False
"""
    assert rule_ids(src, select=["FLC006"]) == ["FLC006"]


def test_flc006_paged_claim_without_scan_fires():
    src = _ROOT + """
class Bad(Strategy):
    supports_paged_store = True
    fallback_reason = "host loop"
"""
    assert rule_ids(src, select=["FLC006"]) == ["FLC006"]


def test_flc006_conformant_hierarchy_is_clean():
    src = _ROOT + """
class Compiled(Strategy):
    supports_scan = True
    supports_sharded_scan = True

    def post_round(self, t, w, ids, u, stats):
        return False

    def scan_program(self):
        return None


class LoopOnly(Strategy):
    supports_scan = False
    fallback_reason = "selection depends on previous-round losses"


class Inherited(Compiled):
    pass
"""
    assert rule_ids(src, select=["FLC006"]) == []


def test_flc006_reports_inherited_violations():
    # the violation sits on the subclass even when the claim is inherited
    src = _ROOT + """
class Base(Strategy):
    supports_sharded_scan = True


class Child(Base):
    supports_scan = True
"""
    # Base: sharded without scan; Child resolves scan=True through its own
    # attr so only Base fires
    ids = rule_ids(src, select=["FLC006"])
    assert ids == ["FLC006"]


def test_flc006_disable_comment_on_class_line_suppresses():
    src = _ROOT + """
class Bad(Strategy):  # flcheck: disable=FLC006
    supports_sharded_scan = True
"""
    assert rule_ids(src, select=["FLC006"]) == []


def test_flc006_non_strategy_classes_ignored():
    src = """
class Widget:
    supports_scan = False

    def process_update(self, u):
        return u
"""
    assert rule_ids(src, select=["FLC006"]) == []


def test_conformance_table_lists_shipped_strategies():
    conf = ConformancePass()
    from repro.analysis.base import SourceFile

    for path in iter_python_files([os.path.join(REPO, "src")]):
        with open(path, "r", encoding="utf-8") as fh:
            conf.check(SourceFile(path, fh.read()))
    table = conf.render_conformance_table()
    for name in ("FLrce", "FedAvg", "Fedprox", "PyramidFL"):
        assert f"`{name}`" in table
    # the machine-readable opt-out reason is rendered, not elided
    assert "cannot be precomputed ahead of a chunk" in table


# ---------------------------------------------------------------------------
# FLC007 staleness-arithmetic
# ---------------------------------------------------------------------------
def test_flc007_inline_departure_subtraction_fires():
    src = """
    def ingest(t_land, t_depart):
        tau = t_land - t_depart
        return tau
    """
    assert rule_ids(src, select=["FLC007"]) == ["FLC007"]


def test_flc007_buffer_field_and_augassign_fire():
    src = """
    def weights(abuf, t):
        tau = t - abuf["depart"]
        t -= arrival_round
        return tau
    """
    assert rule_ids(src, select=["FLC007"]) == ["FLC007", "FLC007"]


def test_flc007_inside_staleness_of_is_exempt():
    src = """
    def staleness_of(t_depart, t_land):
        return t_land - t_depart
    """
    assert rule_ids(src, select=["FLC007"]) == []


def test_flc007_comparisons_and_additions_are_clean():
    src = """
    import jax.numpy as jnp
    from repro.fl.async_rounds import staleness_of

    def round_step(abuf, t32, delays):
        land = t32 + delays
        arrived = abuf["land"].reshape(-1) == t32
        tau = staleness_of(abuf["depart"].reshape(-1), t32)
        return land, arrived, tau
    """
    assert rule_ids(src, select=["FLC007"]) == []


def test_flc007_unrelated_subtraction_is_clean():
    src = """
    def bench(t0, t1):
        return t1 - t0
    """
    assert rule_ids(src, select=["FLC007"]) == []


def test_flc007_disable_comment_suppresses():
    src = """
    def plot(arrival_ts, start_ts):
        return arrival_ts - start_ts  # flcheck: disable=FLC007
    """
    assert rule_ids(src, select=["FLC007"]) == []


# ---------------------------------------------------------------------------
# runner / CLI / self-application
# ---------------------------------------------------------------------------
def test_rule_registry_is_complete():
    assert sorted(RULES) == [f"FLC00{i}" for i in range(1, 8)]
    table = render_rule_table()
    for rid, info in RULES.items():
        assert rid in table and info.name in table


def test_findings_sorted_and_rendered_with_fixit():
    src = """
    import time

    t1 = time.time()
    t0 = time.time()
    """
    findings = lint_text(textwrap.dedent(src), "x.py", select=["FLC005"])
    assert [f.line for f in findings] == sorted(f.line for f in findings)
    rendered = findings[0].render()
    assert rendered.startswith("x.py:") and "fix:" in rendered


def test_select_by_rule_name():
    src = "import time\nt = time.time()\n"
    assert rule_ids(src, select=["wall-clock"]) == ["FLC005"]
    assert rule_ids(src, select=["FLC001"]) == []


def _run_cli(*argv):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *argv],
        cwd=REPO, env=env, capture_output=True, text=True,
    )


def test_cli_self_application_is_clean():
    """The acceptance criterion: the repo passes its own checker."""
    proc = _run_cli("src/", "benchmarks/")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "flcheck: clean" in proc.stdout


def test_cli_reports_findings_with_exit_1(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nt = time.time()\n")
    proc = _run_cli(str(bad))
    assert proc.returncode == 1
    assert "FLC005" in proc.stdout and "fix:" in proc.stdout
    assert "1 finding(s)" in proc.stderr


def test_cli_rules_and_conformance_table():
    proc = _run_cli("--rules")
    assert proc.returncode == 0 and "FLC006" in proc.stdout
    proc = _run_cli("--conformance-table", "src/")
    assert proc.returncode == 0 and "`PyramidFL`" in proc.stdout


# ---------------------------------------------------------------------------
# compile_guard: the runtime sentinel
# ---------------------------------------------------------------------------
def test_compile_counter_counts_fresh_compile_once():
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.arange(8, dtype=jnp.float32)  # eager ops happen OUTSIDE the with
    with CompileCounter() as cc:
        fn(x).block_until_ready()
    assert cc.compiles == 1
    with CompileCounter() as cc2:
        fn(x).block_until_ready()          # cache hit: no compile event
    assert cc2.compiles == 0


def test_compile_counter_nests_and_deltas():
    import jax
    import jax.numpy as jnp

    x = jnp.arange(4, dtype=jnp.float32)
    f = jax.jit(lambda v: v - 3.0)
    g = jax.jit(lambda v: v / 2.0)
    with CompileCounter() as outer:
        f(x).block_until_ready()
        with outer.delta() as d:
            g(x).block_until_ready()
    assert d.compiles == 1
    assert outer.compiles == 2


def test_assert_compiles_diagnostic():
    cc = CompileCounter()
    cc._count = 3
    with pytest.raises(AssertionError, match="silent-recompile"):
        assert_compiles(cc, 1, "unit")
    assert_compiles(cc, 3, "unit")  # exact match passes


def test_scan_driver_reports_single_chunk_compile():
    """End-to-end: the scan driver's own sentinel stats say the chunk
    program compiled exactly once for a plain FedAvg job."""
    from repro.data import make_federated_classification
    from repro.fl import run_federated
    from repro.fl.baselines import FedAvg
    from repro.models.cnn import MLPClassifier

    ds = make_federated_classification(
        num_clients=6, alpha=0.5, num_samples=480, num_eval=96,
        feature_dim=8, num_classes=3, seed=0,
    )
    model = MLPClassifier(feature_dim=8, num_classes=3, hidden=(16,))
    res = run_federated(
        model, ds, FedAvg(6, 3, 1, seed=0), max_rounds=9,
        learning_rate=0.1, batch_size=16, seed=0,
        driver="scan", scan_chunk_rounds=3,
    )
    assert res.driver_stats["compiles_chunk"] == 1
    assert res.driver_stats["compiles_total"] >= 1


_CACHED_JOB = """
import json
from repro.data import make_federated_classification
from repro.fl import run_federated
from repro.fl.baselines import FedAvg
from repro.launch import compile_cache
from repro.models.cnn import MLPClassifier

path = compile_cache()
ds = make_federated_classification(
    num_clients=6, alpha=0.5, num_samples=480, num_eval=96,
    feature_dim=8, num_classes=3, seed=0,
)
res = run_federated(
    MLPClassifier(feature_dim=8, num_classes=3, hidden=(16,)), ds,
    FedAvg(6, 3, 1, seed=0), max_rounds=6, learning_rate=0.1,
    batch_size=16, seed=0, driver="scan", scan_chunk_rounds=3,
)
print(json.dumps({"path": path, "compiles_chunk": res.driver_stats["compiles_chunk"]}))
"""


def test_chunk_compile_counts_once_on_cold_and_warm_cache(tmp_path):
    """A program loaded from the persistent cache fires no backend-compile
    event; the sentinel counts the load, so a warm process still reports
    exactly one chunk program (in its own process: the cache is global)."""
    import json

    cache = tmp_path / "jax_cache"
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_COMPILATION_CACHE_DIR=str(cache))
    runs = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", _CACHED_JOB], cwd=tmp_path,
                              env=env, capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr[-2000:]
        runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
        assert any(cache.iterdir())
    assert runs[0] == runs[1] == {"path": str(cache), "compiles_chunk": 1}


def test_compile_cache_defaults_to_checkout_dir(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = ("import jax; from repro.launch import compile_cache; p = compile_cache(); "
            "print(p, jax.config.jax_compilation_cache_dir)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = os.path.join(REPO, ".jax_cache")
    assert proc.stdout.split() == [want, want]


# ---------------------------------------------------------------------------
# docs sync: docs/invariants.md rule table ≡ code
# ---------------------------------------------------------------------------
def test_invariants_doc_matches_rule_table():
    path = os.path.join(REPO, "docs", "invariants.md")
    with open(path) as f:
        doc = f.read()
    assert DOC_BEGIN_MARKER in doc and DOC_END_MARKER in doc
    embedded = doc.split(DOC_BEGIN_MARKER, 1)[1].split(DOC_END_MARKER, 1)[0].strip()
    assert embedded == render_rule_table(), (
        "docs/invariants.md is stale — regenerate with "
        "`PYTHONPATH=src python -m repro.analysis --rules` and paste the "
        "table between the markers"
    )
    # every rule's doc section exists
    for rid in RULES:
        assert f"### {rid}" in doc, rid
