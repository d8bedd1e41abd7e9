"""The cohort program's step loop runs to the cohort's last real step.

``BatchedCohortTrainer``'s program loops the client-vmapped SGD step over
the step axis only ``steps_to_run(valid)`` times: the steps after the last
one any row holds are the bucket's slack, and each would be an exact no-op.
These tests hold the loop to the fixed-length form it replaced — a ``vmap``
over clients of a ``lax.scan`` over all S steps, kept here as the
reference — on a ragged cohort (rows of 3, 17 and 64 real steps and one of
padding, in a 128-step bucket), for the batched trainer and the sharded
trainer on a (1, 1) mesh and, with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, on a (2, 4) mesh
whose two ``data`` shards run different trip counts.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import make_federated_classification
from repro.fl import FLrce, run_federated, scan_driver
from repro.fl.client import (
    BatchedCohortTrainer,
    ShardedCohortTrainer,
    stack_freeze_flags,
    stack_variant_trees,
    steps_to_run,
    tree_sub,
)
from repro.launch.mesh import make_debug_mesh
from repro.models.cnn import MLPClassifier, param_count

MULTI = jax.device_count() >= 8
LR, B, S, FEAT, CLASSES = 0.1, 4, 128, 6, 3
REAL_STEPS = (3, 17, 64, 0)          # the last row is all padding
VARIANTS = ("plain", "prox", "mask_freeze")


def reference_train(model, lr, global_params, xs, ys, ws, valid, mask, freeze,
                    prox_mu, *, use_prox, has_mask):
    """The fixed-length cohort program: every client scans all S steps."""

    def one_client(xs, ys, ws, valid, mask, freeze, prox_mu):
        def step(params, inp):
            x, y, w, v = inp

            def loss_fn(p):
                q = jax.tree_util.tree_map(lambda a, m: a * m, p, mask) if has_mask else p
                per = jax.vmap(lambda xi, yi: model.loss(q, xi[None], yi[None]))(x, y)
                base = jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)
                if use_prox:
                    sq = sum(
                        jnp.sum(jnp.square(a - b))
                        for a, b in zip(jax.tree_util.tree_leaves(q),
                                        jax.tree_util.tree_leaves(global_params))
                    )
                    base = base + 0.5 * prox_mu * sq
                return base

            loss, grads = jax.value_and_grad(loss_fn)(params)
            if has_mask:
                grads = jax.tree_util.tree_map(lambda g, m: g * m, grads, mask)
            grads = jax.tree_util.tree_map(lambda g, f: g * (f * v), grads, freeze)
            return jax.tree_util.tree_map(lambda a, g: a - lr * g, params, grads), loss

        final, losses = jax.lax.scan(step, global_params, (xs, ys, ws, valid))
        update = tree_sub(final, global_params)
        if has_mask:
            update = jax.tree_util.tree_map(lambda u, m: u * m, update, mask)
        return update, losses

    updates, losses = jax.vmap(
        one_client, in_axes=(0, 0, 0, 0, 0 if has_mask else None, 0, 0)
    )(xs, ys, ws, valid, mask, freeze, prox_mu)
    p = xs.shape[0]
    flat = jnp.concatenate(
        [jnp.reshape(l, (p, -1)).astype(jnp.float32)
         for l in jax.tree_util.tree_leaves(updates)], axis=1,
    )
    return updates, flat, losses


@pytest.fixture(scope="module")
def model():
    return MLPClassifier(feature_dim=FEAT, num_classes=CLASSES, hidden=(8,))


def cohort(real_steps, s):
    """A (P, S) cohort whose row k holds ``real_steps[k]`` real steps, the
    last of them a partial batch, then padding."""
    rng = np.random.default_rng(0)
    p = len(real_steps)
    xs = np.zeros((p, s, B, FEAT), np.float32)
    ys = np.zeros((p, s, B), np.int32)
    ws = np.zeros((p, s, B), np.float32)
    valid = np.zeros((p, s), np.float32)
    for k, n in enumerate(real_steps):
        xs[k, :n] = rng.normal(size=(n, B, FEAT))
        ys[k, :n] = rng.integers(0, CLASSES, size=(n, B))
        ws[k, :n] = 1.0
        if n:
            ws[k, n - 1, B // 2:] = 0.0
            xs[k, n - 1, B // 2:] = 0.0
        valid[k, :n] = 1.0
    return xs, ys, ws, valid


def args_for(model, params, variant, arrays):
    xs, ys, ws, valid = arrays
    p = xs.shape[0]
    fracs = [0.5, 0.0, 0.5, 0.0] if variant == "mask_freeze" else [0.0] * p
    masks = [None] * p
    if variant == "mask_freeze":
        masks = [jax.tree_util.tree_map(
            lambda a, k=k: (jax.random.uniform(jax.random.PRNGKey(k), a.shape) > 0.3)
            .astype(a.dtype), params) for k in range(p)]
    mask, has_mask = stack_variant_trees(masks, params)
    mus = [0.05, 0.1, 0.2, 0.3] if variant == "prox" else [0.0] * p
    args = (params, jnp.asarray(xs), jnp.asarray(ys), jnp.asarray(ws),
            jnp.asarray(valid), mask if has_mask else {},
            stack_freeze_flags(params, fracs), jnp.asarray(mus, jnp.float32))
    return args, dict(use_prox=variant == "prox", has_mask=has_mask)


def run_reference(model, args, kw):
    return jax.jit(functools.partial(reference_train, model, LR, **kw))(*args)


def check_against_reference(train, model, variant, arrays):
    params = model.init(jax.random.PRNGKey(1))
    args, kw = args_for(model, params, variant, arrays)
    valid = np.asarray(arrays[3])
    want_upd, want_flat, want_loss = run_reference(model, args, kw)
    got_upd, got_flat, got_loss = train(args, kw)
    for a, b in zip(jax.tree_util.tree_leaves(got_upd), jax.tree_util.tree_leaves(want_upd)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(got_flat), np.asarray(want_flat))
    got_loss, want_loss = np.asarray(got_loss), np.asarray(want_loss)
    assert got_loss.shape == valid.shape
    real = valid > 0
    np.testing.assert_array_equal(got_loss[real], want_loss[real])
    return got_loss, want_loss


@pytest.mark.parametrize("variant", VARIANTS)
def test_batched_ragged_cohort_matches_fixed_length(model, variant):
    trainer = BatchedCohortTrainer(model, LR, B)
    arrays = cohort(REAL_STEPS, S)
    got_loss, want_loss = check_against_reference(
        lambda args, kw: trainer._train(*args, **kw), model, variant, arrays
    )
    # the steps past the cohort's last real one did not run: they read 0,
    # where the fixed-length form ran them (under prox, a padded step's
    # loss is the proximal term, not 0)
    n_run = max(REAL_STEPS)
    assert (got_loss[:, n_run:] == 0.0).all()
    if variant == "prox":
        assert (want_loss[:3, n_run:] > 0.0).all()
        # a row that ends sooner still runs (gated) to the cohort's end
        np.testing.assert_array_equal(got_loss[:, :n_run], want_loss[:, :n_run])


@pytest.mark.parametrize("variant", VARIANTS)
def test_sharded_one_by_one_mesh_matches_fixed_length(model, variant):
    trainer = ShardedCohortTrainer(model, LR, B, make_debug_mesh(1, 1))
    arrays = cohort(REAL_STEPS, S)

    def train(args, kw):
        return trainer._sharded_train(kw["use_prox"], kw["has_mask"])(*args)

    got_loss, _ = check_against_reference(train, model, variant, arrays)
    assert (got_loss[:, max(REAL_STEPS):] == 0.0).all()


@pytest.mark.multidevice
@pytest.mark.skipif(not MULTI, reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
@pytest.mark.parametrize("variant", VARIANTS)
def test_sharded_data_shards_run_their_own_trip_counts(model, variant):
    """On a (2, 4) mesh the first ``data`` shard holds rows of 3 and 17
    steps and runs 17; the second holds 64 and padding and runs 64."""
    trainer = ShardedCohortTrainer(model, LR, B, make_debug_mesh(2, 4))
    arrays = cohort(REAL_STEPS, S)

    def train(args, kw):
        return trainer._sharded_train(kw["use_prox"], kw["has_mask"])(*args)

    got_loss, _ = check_against_reference(train, model, variant, arrays)
    assert (got_loss[:2, 17:] == 0.0).all()
    assert (got_loss[2:, 64:] == 0.0).all()
    if variant == "prox":
        assert (got_loss[0, 3:17] > 0.0).all()      # gated, but run


def test_trip_count_is_the_cohorts_last_real_step():
    valid = jnp.asarray(cohort(REAL_STEPS, S)[3])
    assert int(steps_to_run(valid)) == max(REAL_STEPS)
    assert steps_to_run(valid).dtype == jnp.int32
    assert int(steps_to_run(jnp.zeros((3, S)))) == 0
    assert int(steps_to_run(valid[:2])) == 17
    # the last real step, not the count: trailing padding is what is cut
    holes = jnp.asarray([[1, 0, 1, 0, 0], [0, 0, 0, 0, 0]], jnp.float32)
    assert int(steps_to_run(holes)) == 3


@pytest.mark.parametrize("variant", VARIANTS)
def test_cohort_of_full_rows_runs_every_step(model, variant):
    """No bucket slack: S is the cohort's largest client, so all S run."""
    trainer = BatchedCohortTrainer(model, LR, B)
    arrays = cohort((40, 40, 40, 40), 40)
    assert int(steps_to_run(jnp.asarray(arrays[3]))) == 40
    got_loss, _ = check_against_reference(
        lambda args, kw: trainer._train(*args, **kw), model, variant, arrays
    )
    assert (got_loss[:, -1] > 0.0).all()


def test_one_chunk_program_for_rounds_of_different_cohort_maxima():
    """Rounds whose cohorts end at different steps share the chunk's one
    program: the trip count is data, not shape.  ``steps_executed`` follows
    each round's cohort maximum; ``steps_bucket`` the chunk's bucket."""
    ds = make_federated_classification(
        num_clients=8, alpha=0.2, num_samples=800, num_eval=160,
        feature_dim=8, num_classes=3, seed=2,
    )
    model = MLPClassifier(feature_dim=8, num_classes=3, hidden=(16,))
    dim = param_count(model.init(jax.random.PRNGKey(0)))
    built = {}
    real = scan_driver.build_chunk_schedule

    def recording(sizes, epochs, batch_size, t0, rng_for, **kw):
        sched = real(sizes, epochs, batch_size, t0, rng_for, **kw)
        built[t0] = (np.asarray(kw["client_ids"]), sched)
        return sched

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(scan_driver, "build_chunk_schedule", recording)
        res = run_federated(
            model, ds, FLrce(8, 3, 1, dim=dim, es_threshold=2.0, seed=0),
            driver="scan", max_rounds=6, scan_chunk_rounds=3,
            learning_rate=0.1, batch_size=16, seed=0,
        )
    st = res.driver_stats
    assert st["compiles_chunk"] == 1
    maxima, bucket = [], 0
    for rec in res.records:
        t0 = rec.t - rec.t % 3
        cols, sched = built[t0]
        rows = sched.step_valid[rec.t - t0, np.searchsorted(cols, rec.selected)]
        maxima.append(int(np.asarray(steps_to_run(jnp.asarray(rows)))))
        bucket += 3 * sched.num_steps
    assert len(set(maxima)) > 1, maxima
    assert st["steps_executed"] == 3 * sum(maxima)
    assert st["steps_bucket"] == bucket
    assert st["steps_useful"] <= st["steps_executed"] < st["steps_bucket"]
