"""Donated-buffer regressions (no-copy carries and round buffers).

Three donation sites must actually alias in place (checked by buffer id —
XLA:CPU honors input-output aliasing, so pointer equality is exact evidence)
and mark their inputs deleted:

* the scan driver's chunk carry (``_ChunkRunner`` jits with
  ``donate_argnums=(0, 1, 2, 3, 4)``): the flat model, the async arrival
  buffer, the cross-chunk stop flag and the accuracy scalar update in
  place chunk over chunk;
* the loop engines' flat (P, D) update buffer through the jitted
  ``update_transform`` application (``donate_argnums=(2,)``);
* ``BatchedCohortTrainer``'s (P, S) step-validity plan buffer, which aliases
  the (P, S) loss-trace output.

A lowering-level check asserts the donation is recorded in the compiled
artifact (buffer-donor/aliasing markers), so a silently dropped
``donate_argnums`` cannot pass by accident of allocator reuse.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distributed import flatten_pytree
from repro.data import DeviceClientStore, build_chunk_schedule, make_federated_classification
from repro.fl.baselines import Fedcom, FedAvg, QuantizedFL
from repro.fl.client import BatchedCohortTrainer, build_cohort_plan, client_batch_rng, stack_freeze_flags
from repro.fl.scan_driver import _ChunkRunner
from repro.models.cnn import MLPClassifier, param_count


@pytest.fixture(scope="module")
def tiny_fed():
    ds = make_federated_classification(
        num_clients=8, alpha=0.2, num_samples=800, num_eval=160,
        feature_dim=8, num_classes=3, seed=2,
    )
    return ds, MLPClassifier(feature_dim=8, num_classes=3, hidden=(16,))


# ---------------------------------------------------------------------------
# scan chunk carry
# ---------------------------------------------------------------------------
def test_chunk_carry_donated_in_place(tiny_fed):
    """The chunk's flat-w carry output aliases the input buffer (no copy)
    and the donated inputs are deleted."""
    ds, model = tiny_fed
    params = model.init(jax.random.PRNGKey(0))
    w, unflatten = flatten_pytree(params)
    w = jax.device_put(w, next(iter(w.devices())))
    store = DeviceClientStore.from_dataset(ds)
    strat = FedAvg(8, 3, 1, seed=0)
    runner = _ChunkRunner(
        model, store, unflatten, strat.scan_program(), None,
        learning_rate=0.1, batch_size=16, clients_per_round=3,
        eval_every=1, max_rounds=2,
        eval_x=jnp.asarray(ds.eval_x), eval_y=jnp.asarray(ds.eval_y),
        feat_shape=ds.x.shape[1:],
    )
    r, m = 2, 8
    sched = build_chunk_schedule(
        store.sizes_host, np.ones((r, m), np.int32), 16, 0,
        lambda t, cid: client_batch_rng(0, t, cid),
    )
    freeze_rounds = [stack_freeze_flags(params, [0.0] * 3) for _ in range(r)]
    xs = (
        jnp.arange(r, dtype=jnp.int32),
        jnp.zeros(r, jnp.float32),
        # full-universe candidates ⇒ host slots ≡ global ids
        jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32),
        jnp.asarray(sched.batch_idx),
        jnp.asarray(sched.sample_w),
        jnp.asarray(sched.step_valid),
        jnp.zeros((r, m), jnp.float32),
        {},
        jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *freeze_rounds),
    )
    cand = jnp.arange(m, dtype=jnp.int32)
    dev = next(iter(w.devices()))
    last_acc = jax.device_put(jnp.float32(0.0), dev)
    stopped = jax.device_put(jnp.asarray(False), dev)
    ptr_w = w.unsafe_buffer_pointer()
    ptr_cand = cand.unsafe_buffer_pointer()
    w2, sc2, abuf2, es2, acc2, outs = runner.run_chunk(
        w, {}, {}, stopped, last_acc, cand, None, xs, False, False
    )
    assert w2.shape == w.shape
    assert w2.unsafe_buffer_pointer() == ptr_w          # aliased in place
    assert w.is_deleted()                                # donated input gone
    assert stopped.is_deleted()                          # stop flag donated too
    # the candidate remap is a per-chunk INPUT, never donated (two in-flight
    # pipelined chunks each hold their own)
    assert not cand.is_deleted()
    assert cand.unsafe_buffer_pointer() == ptr_cand
    # and the chunk really ran: both rounds produced valid outputs
    assert np.all(np.asarray(outs["valid"]))
    # a second chunk donates the returned carry the same way
    ptr_w2 = w2.unsafe_buffer_pointer()
    w3, *_ = runner.run_chunk(w2, sc2, abuf2, es2, acc2, cand, None, xs,
                              False, False)
    assert w3.unsafe_buffer_pointer() == ptr_w2
    assert w2.is_deleted()


# ---------------------------------------------------------------------------
# loop engines' flat (P, D) buffer through the jitted update transform
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cls,kw", [
    (Fedcom, {"keep_frac": 0.25}),
    (QuantizedFL, {}),
])
def test_update_transform_donates_flat_buffer(tiny_fed, cls, kw):
    _, model = tiny_fed
    params = model.init(jax.random.PRNGKey(0))
    d = param_count(params)
    transform = cls(8, 3, 1, seed=0, **kw).update_transform(params)
    apply_transform = jax.jit(transform, donate_argnums=(2,))
    # the donation is recorded at lowering time, not an allocator accident
    lowered = apply_transform.lower(
        jnp.int32(0), jnp.zeros(3, jnp.int32), jnp.zeros((3, d), jnp.float32)
    ).as_text()
    assert ("jax.buffer_donor" in lowered) or ("tf.aliasing_output" in lowered)
    u = jnp.full((3, d), 0.1, jnp.float32)
    ptr = u.unsafe_buffer_pointer()
    v = apply_transform(jnp.int32(0), jnp.asarray([0, 1, 2], jnp.int32), u)
    assert v.unsafe_buffer_pointer() == ptr
    assert u.is_deleted()


# ---------------------------------------------------------------------------
# BatchedCohortTrainer: (P, S) step validity aliases the (P, S) loss trace
# ---------------------------------------------------------------------------
def test_batched_trainer_donates_step_validity(tiny_fed):
    ds, model = tiny_fed
    params = model.init(jax.random.PRNGKey(0))
    trainer = BatchedCohortTrainer(model, 0.1, 16)
    ids = [0, 1, 2]
    plan = build_cohort_plan(
        [ds.client_data(c) for c in ids], [1, 1, 1], 16,
        [client_batch_rng(0, 0, c) for c in ids],
    )
    freeze = stack_freeze_flags(params, [0.0] * 3)
    valid = jnp.asarray(plan.step_valid)
    args = (params, jnp.asarray(plan.x), jnp.asarray(plan.y),
            jnp.asarray(plan.sample_w), valid, {}, freeze, jnp.zeros(3))
    # the donation is recorded at lowering time (whether XLA then aliases
    # the same-shaped loss output onto it is its call — with 8 virtual
    # devices visible it sometimes chooses not to, so pointer equality
    # would be flaky here; input deletion is the donation contract)
    lowered = trainer._train.lower(*args, use_prox=False, has_mask=False).as_text()
    assert ("jax.buffer_donor" in lowered) or ("tf.aliasing_output" in lowered)
    _, _, losses = trainer._train(*args, use_prox=False, has_mask=False)
    assert losses.shape == plan.step_valid.shape
    assert valid.is_deleted()
    # train_cohort (the loop engines' entry point) still works end to end on
    # top of the donation — the plan's host arrays are untouched
    _, flat, stats = trainer.train_cohort(
        params, plan, prox_mus=[0.0] * 3, masks=[None] * 3,
        freeze_fracs=[0.0] * 3,
    )
    assert np.isfinite(np.asarray(flat)).all()
    assert plan.step_valid.sum() > 0
