"""Hypothesis property tests, consolidated from every suite.

``hypothesis`` is a dev-only dependency (``pip install -e ".[dev]"``); when it
is absent this module skips cleanly via ``pytest.importorskip`` and the rest
of the suite — which is hypothesis-free — still runs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import assume, given, settings, strategies as st

from repro.core import cossim, orthdist, relationship_row, select_clients, should_stop
from repro.core.relationship import _EPS
from repro.data.partition import dirichlet_label_partition
from repro.fl.aggregation import aggregation_weights, staleness_weights
from repro.fl.async_rounds import default_decay
from repro.kernels import ops

finite_vec = st.lists(
    st.floats(-10, 10, allow_nan=False, width=32), min_size=2, max_size=8
)


# ---------------------------------------------------------------------------
# relationship modeling (Eq. 5/6, Alg. 1)
# ---------------------------------------------------------------------------
@settings(max_examples=50, deadline=None)
@given(finite_vec, finite_vec)
def test_cossim_symmetric_and_bounded(a, b):
    n = min(len(a), len(b))
    u, v = jnp.asarray(a[:n]), jnp.asarray(b[:n])
    c1, c2 = float(cossim(u, v)), float(cossim(v, u))
    assert c1 == pytest.approx(c2, abs=1e-5)
    assert -1.0 - 1e-5 <= c1 <= 1.0 + 1e-5


@settings(max_examples=30, deadline=None)
@given(finite_vec, st.floats(0.1, 100.0))
def test_cossim_scale_invariant(a, s):
    """Holds wherever both norm products, ``||u||·||s·u||`` and
    ``||u||·||u||``, clear cossim's 1e-12 zero-vector guard; below it the
    guard damps the cosine toward 0 by design (e.g. ``u = [0, 2**-24]``),
    so such vectors are outside the property.  The 0.1% margin covers the
    fp32 rounding of the norms."""
    u = jnp.asarray(a)
    assume(float(jnp.vdot(u, u)) * min(s, 1.0) > _EPS * 1.001)
    assert float(cossim(u, u * s)) == pytest.approx(float(cossim(u, u)), abs=1e-4)


@settings(max_examples=30, deadline=None)
@given(finite_vec, st.floats(0.5, 20.0))
def test_orthdist_direction_scale_invariant(a, s):
    """orthdist depends only on the ray, not the direction's magnitude."""
    n = len(a)
    x = jnp.asarray(a)
    anchor = jnp.zeros(n)
    direction = jnp.ones(n)
    d1 = float(orthdist(x, anchor, direction))
    d2 = float(orthdist(x, anchor, direction * s))
    assert d1 == pytest.approx(d2, rel=1e-4, abs=1e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 6), st.integers(2, 8), st.integers(0, 10))
def test_relationship_row_bounded(m, d, t):
    rng = np.random.default_rng(m * 100 + d)
    updates = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    anchors = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    last = jnp.asarray(rng.integers(-1, t + 1, size=m), jnp.int32)
    row = relationship_row(
        0,
        updates[0],
        jnp.asarray(rng.normal(size=(d,)), jnp.float32),
        updates,
        anchors,
        last,
        t,
        jnp.zeros((m,), jnp.float32),
    )
    assert np.all(np.asarray(row) <= 1.0 + 1e-5)
    assert np.all(np.asarray(row) >= -1.0 - 1e-5)


# ---------------------------------------------------------------------------
# selection (Alg. 2) and early stopping (Alg. 3)
# ---------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(st.integers(2, 20), st.integers(1, 10), st.integers(0, 200))
def test_select_returns_p_distinct(m, p, t):
    if p > m:
        p = m
    rng = jax.random.PRNGKey(t)
    h = jnp.asarray(np.random.default_rng(m).normal(size=m), jnp.float32)
    ids, exploited = select_clients(rng, h, t, p)
    ids = np.asarray(ids)
    assert len(ids) == p
    assert len(set(ids.tolist())) == p
    assert ids.min() >= 0 and ids.max() < m


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 8), st.floats(0.0, 4.0))
def test_es_monotone_in_psi(p, psi):
    """If ES fires at threshold psi it must also fire at any psi' < psi."""
    rng = np.random.default_rng(p)
    u = jnp.asarray(rng.normal(size=(p, 5)), jnp.float32)
    d_hi = should_stop(u, psi=psi, is_exploit_round=True)
    d_lo = should_stop(u, psi=psi * 0.5, is_exploit_round=True)
    if d_hi.stop:
        assert d_lo.stop


@settings(max_examples=40, deadline=None)
@given(st.integers(2, 200), st.integers(1, 6), st.integers(0, 100))
def test_es_conflicts_is_exact_pair_ratio(p, d, seed):
    """conflicts == conflict_pairs / p exactly: the pair count is the
    primitive integer quantity, never re-derived through a lossy
    normalize/denormalize round-trip."""
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(p, d)), jnp.float32)
    dec = should_stop(u, psi=0.5, is_exploit_round=True)
    assert isinstance(dec.conflict_pairs, int)
    assert dec.conflicts == dec.conflict_pairs / p
    assert 0 <= dec.conflict_pairs <= p * (p - 1)


# ---------------------------------------------------------------------------
# data partitioning
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(st.integers(2, 12), st.floats(0.05, 5.0), st.integers(0, 5))
def test_label_partition_covers_everything(clients, alpha, seed):
    labels = np.random.default_rng(seed).integers(0, 5, size=500)
    parts = dirichlet_label_partition(labels, clients, alpha=alpha, seed=seed)
    allidx = np.concatenate(parts)
    assert len(allidx) == 500
    assert len(np.unique(allidx)) == 500  # disjoint cover


# ---------------------------------------------------------------------------
# aggregation (Eq. 4)
# ---------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(1, 1000), min_size=1, max_size=10))
def test_aggregation_weights_simplex(counts):
    w = aggregation_weights(counts)
    assert w.sum() == pytest.approx(1.0, abs=1e-5)
    assert (w >= 0).all()


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(st.integers(100, 3000), st.floats(0.05, 0.9))
def test_topk_mask_sparsity_property(d, keep):
    rng = np.random.default_rng(d)
    u = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    out = np.asarray(ops.topk_mask(u, keep_frac=keep, block_d=512))
    # kept entries are a subset of the input entries
    nz = out != 0
    np.testing.assert_array_equal(out[nz], np.asarray(u)[nz])
    # block-local keep fraction is ~keep, up to padding slack in the final
    # block (zero-padded entries tie at the threshold and inflate the count)
    slack = 512 / d + 0.02
    assert nz.mean() <= min(1.0, keep + slack)


# ---------------------------------------------------------------------------
# staleness-weighted aggregation (async rounds, delayed Eq. 4)
# ---------------------------------------------------------------------------
_staleness_case = st.lists(
    st.tuples(st.integers(1, 1000), st.integers(0, 5)), min_size=1, max_size=10
)


@settings(max_examples=30, deadline=None)
@given(_staleness_case)
def test_staleness_weights_simplex(case):
    counts = [n for n, _ in case]
    taus = [t for _, t in case]
    w = staleness_weights(counts, taus, default_decay)
    assert w.sum() == pytest.approx(1.0, abs=1e-5)
    assert (w >= 0).all()


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(1, 1000), min_size=1, max_size=10))
def test_staleness_weights_tau_zero_recovers_eq4_bitwise(counts):
    """decay(0) == 1.0 multiplies every count by exactly 1.0: the staleness
    weighting at τ=0 is BITWISE plain Eq. 4 — the host-side statement of the
    async ≡ sync equivalence spine."""
    w_async = staleness_weights(counts, [0] * len(counts), default_decay)
    w_sync = aggregation_weights(counts)
    assert np.array_equal(w_async, w_sync)


@settings(max_examples=30, deadline=None)
@given(_staleness_case, st.integers(0, 6))
def test_staleness_weights_permutation_invariant(case, seed):
    """Weights follow the (count, τ) pair, not the arrival-slot order — the
    flattened ring buffer may present arrivals in any slot permutation."""
    counts = np.asarray([n for n, _ in case], np.float64)
    taus = np.asarray([t for _, t in case])
    perm = np.random.default_rng(seed).permutation(len(case))
    w = staleness_weights(counts, taus, default_decay)
    w_perm = staleness_weights(counts[perm], taus[perm], default_decay)
    assert np.array_equal(w[perm], w_perm)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 1000), st.integers(0, 5), st.integers(1, 5))
def test_staleness_weights_monotone_in_tau(count, tau, extra):
    """For a nonincreasing decay, a staler copy of the same update never
    outweighs the fresher one (τ strictly increases ⇒ weight strictly
    decreases under 1/(1+τ))."""
    w = staleness_weights([count, count], [tau, tau + extra], default_decay)
    assert w[0] > w[1]
