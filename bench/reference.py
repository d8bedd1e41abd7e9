"""A plain reference of FLrce's first rounds, and the benchmark's weights.

Written from the paper's equations in straightforward ``jax.numpy``; it
imports nothing of the program.  The model's own mathematics (weights, a
batch's loss, the count of correct answers) comes from the cell's model
module, ``bench/models/<model>.py``.  It follows a job from its seed for a few
rounds, one client at a time, and records what the comparison reads:

* Alg. 2 selection: the explore flip, the explore draw, and in an exploit
  round the top P by the heuristic H (ties to the lower id), with the
  margin between the P-th and the (P+1)-th value, so that the comparison
  can tell a near-tie that rounding decides from a wrong choice;
* each selected client's local SGD over the same shuffled batches the
  program's schedule holds (one ``permutation(n)`` per epoch from the
  ``(seed, round, client)`` stream), its mean step loss and its update;
* Eq. 4 aggregation (weights proportional to client sizes);
* Alg. 1 ingest: the fresh client's relationship row against every client
  seen so far, Eq. 5 (cosine) for fresh peers and Eq. 6 (orthogonal
  distances to the peer's ray) for stale ones, and its heuristic H (the
  row's sum without the client itself, Eq. 7);
* Alg. 3 in an exploit round: the ordered pairs of the cohort's updates
  whose cosine is negative, counted twice: certainly negative, and
  negative or within ``COS_BAND`` of 0, where rounding may put either sign;
* the eval accuracy of each round's aggregated model.

``precision`` None computes in float32 with every matmul and convolution
at ``Precision.HIGHEST``; "bfloat16" (the control) keeps the trained
weights and the relationship vectors in bfloat16, and the model module
computes in bfloat16 (real-valued samples are cast to it; token ids are
not).
``fault`` plants one of the faults the comparison must catch: "half_batch"
trains every step on the first half of its batch only.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

EPS = 1e-12
# A cosine computed from bfloat16 inputs with float32 sums is off by at most
# 2**-8 of the two norms' product (each product's relative error); pairs
# whose float32 cosine lies within 2.5 times that of 0 may take either sign.
COS_BAND = 0.01


def make_weights(model, cfg: Dict):
    """One jitted call that makes every weight on the device: the trained
    parameters from one seed and the frozen ones (None where the model has
    none) from another."""
    return jax.jit(lambda init_seed, frozen_seed: (
        model.init(cfg, jax.random.PRNGKey(init_seed)),
        model.frozen(cfg, jax.random.PRNGKey(frozen_seed))))


def _flat(tree) -> jax.Array:
    return jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                            for l in jax.tree_util.tree_leaves(tree)])


class Reference:
    def __init__(self, model, cfg: Dict, mix: Dict, precision: Optional[str] = None,
                 fault: Optional[str] = None):
        if precision not in (None, "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.model, self.cfg, self.mix, self.fault = model, cfg, mix, fault
        self.dtype = jnp.float32 if precision is None else jnp.bfloat16
        self.prec = lax.Precision.HIGHEST if precision is None else lax.Precision.DEFAULT
        self.lr = float(mix["learning_rate"])
        self.batch = int(mix["batch_size"])
        self._train = jax.jit(self._train_client)
        self._correct = jax.jit(self._count_correct)

    # -- one client's local SGD ----------------------------------------------
    def _train_client(self, params, frozen, x_all, y_all, gidx, gw, n_steps):
        model, cfg, dtype, prec, lr = self.model, self.cfg, self.dtype, self.prec, self.lr

        def loss_fn(p, xb, yb, wb):
            return model.loss(cfg, p, frozen, xb, yb, wb, dtype, prec)

        def step(i, carry):
            p, total = carry
            loss, g = jax.value_and_grad(loss_fn)(p, x_all[gidx[i]], y_all[gidx[i]], gw[i])
            p = jax.tree_util.tree_map(lambda a, d: (a - lr * d).astype(a.dtype), p, g)
            return p, total + loss

        p0 = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
        p, total = lax.fori_loop(0, n_steps, step, (p0, jnp.float32(0.0)))
        update = jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0)
        return update, total / jnp.maximum(n_steps, 1).astype(jnp.float32)

    def _count_correct(self, params, frozen, xb, yb):
        p = jax.tree_util.tree_map(lambda a: a.astype(self.dtype), params)
        return self.model.correct(self.cfg, p, frozen, xb, yb, self.dtype, self.prec)

    def _accuracy(self, params, frozen, ex, ey, block: int = 1000) -> float:
        correct = 0
        for s in range(0, ex.shape[0], block):
            correct += int(self._correct(params, frozen, ex[s:s + block], ey[s:s + block]))
        return correct / ex.shape[0]

    def _schedule(self, job_seed: int, t: int, cid: int, idx: np.ndarray, s_max: int):
        """The client's shuffled batches as global sample indices, padded to
        ``s_max`` steps: one permutation of its n samples per epoch, drawn
        from the ``(seed, round, client)`` stream, cut into batches."""
        b, e, n = self.batch, int(self.mix["local_epochs"]), len(idx)
        nb = -(-n // b)
        rng = np.random.default_rng(
            np.random.SeedSequence([int(job_seed) & 0xFFFFFFFFFFFFFFFF, int(t), int(cid)]))
        gidx = np.zeros((s_max, b), np.int32)
        gw = np.zeros((s_max, b), np.float32)
        s = 0
        for _ in range(e):
            order = rng.permutation(n)
            for start in range(0, n, b):
                part = order[start:start + b]
                gidx[s, :len(part)] = idx[part]
                gw[s, :len(part)] = 1.0
                s += 1
        if self.fault == "half_batch":
            gw[:, b // 2:] = 0.0
        return gidx, gw, e * nb

    # -- Alg. 1 and Alg. 3 over fixed shapes --------------------------------
    def _relations(self, u, w, vs, as_):
        """For each fresh update u_k (P, D) against every stored slot j
        (S, D): Eq. 5's cosine and Eq. 6's ``1 - d_after / d_before`` with
        ``d = orthdist(point, ray from a_j along v_j)``."""
        hi = self.prec
        vec = lambda a: a.astype(self.dtype).astype(jnp.float32)
        u, w, vs, as_ = vec(u), vec(w), vec(vs), vec(as_)
        vv = jnp.maximum(jnp.sum(vs * vs, axis=1), EPS)              # (S,)
        cos = jnp.dot(u, vs.T, precision=hi) / jnp.maximum(
            jnp.linalg.norm(u, axis=1)[:, None] * jnp.sqrt(vv)[None, :], EPS)

        def orth(r):                                                # r (S, D)
            coef = jnp.sum(r * vs, axis=1) / vv
            return jnp.linalg.norm(r - coef[:, None] * vs, axis=1)

        rel = w[None, :] - as_
        d_before = orth(rel)
        d_after = lax.map(lambda uk: orth(rel + uk[None, :]), u)    # (P, S)
        eq6 = jnp.clip(1.0 - d_after / jnp.maximum(d_before, EPS)[None, :], -1.0, 1.0)
        return cos, eq6

    def _cosines(self, u):
        """(P, P) cosines of the cohort's updates, for Alg. 3."""
        u = u.astype(self.dtype).astype(jnp.float32)
        g = jnp.dot(u, u.T, precision=self.prec)
        n = jnp.sqrt(jnp.maximum(jnp.diagonal(g), EPS))
        return g / jnp.maximum(n[:, None] * n[None, :], EPS)

    # -- the job's first rounds ------------------------------------------------
    def run(self, traffic, params0, frozen, sel_seed: int, job_seed: int, rounds: int) -> Dict:
        """Follow the job for ``rounds`` rounds from the trained weights
        ``params0`` and the frozen ``frozen`` (None where there are none)."""
        mix = self.mix
        m, p = int(mix["clients"]), int(mix["clients_per_round"])
        decay = float(mix["explore_decay"])
        sizes = np.asarray(traffic.sizes, np.int64)
        s_max = int(mix["local_epochs"]) * -(-int(sizes.max()) // self.batch)
        x_all = jnp.asarray(traffic.x.reshape(traffic.x.shape[0], -1))
        y_all = jnp.asarray(traffic.y)
        ex = jnp.asarray(traffic.eval_x.reshape(traffic.eval_x.shape[0], -1))
        ey = jnp.asarray(traffic.eval_y)
        flat = jax.jit(_flat)
        aggregate = jax.jit(lambda w, wt, u: w + jnp.dot(wt, u, precision=self.prec))
        relations = jax.jit(self._relations)
        cosines = jax.jit(self._cosines)

        key = jax.random.PRNGKey(int(sel_seed))
        omega = np.zeros((m, m), np.float32)
        heur = np.zeros(m, np.float64)
        # the V/A/R maps, one slot per client seen, fixed shapes
        n_slots = rounds * p
        slot_of: Dict[int, int] = {}
        slot_last = np.full(n_slots, -1, np.int64)
        slot_client = np.zeros(n_slots, np.int64)
        w = flat(params0)
        vs = jnp.zeros((n_slots, w.shape[0]), jnp.float32)
        as_ = jnp.zeros((n_slots, w.shape[0]), jnp.float32)
        params = params0
        out = {"rounds": [], "models": {0: np.asarray(w)}, "updates": {},
               "omega": {}, "heuristic": {}, "seconds": {}}
        clock = {"train": 0.0, "ingest": 0.0, "eval": 0.0}
        for t in range(rounds):
            t0 = time.perf_counter()
            key, sub = jax.random.split(key)
            k_flip, k_draw = jax.random.split(sub)
            explore = bool(jax.random.uniform(k_flip) < jnp.float32(decay ** t))
            rec: Dict = {"exploited": not explore}
            if explore:
                ids = jnp.sort(jax.random.choice(k_draw, m, shape=(p,), replace=False))
                ids = [int(i) for i in np.asarray(ids)]
            else:
                # Alg. 2's exploit: the top P by H, ties to the lower id
                order = np.lexsort((np.arange(m), -heur))
                ids = sorted(int(i) for i in order[:p])
                rec["margin"] = float(heur[order[p - 1]] - heur[order[p]]) if p < m else math.inf
            rec["ids"] = ids

            upd, losses = [], []
            for cid in ids:
                gidx, gw, n_steps = self._schedule(job_seed, t, cid,
                                                   traffic.client_indices[cid], s_max)
                u, loss = self._train(params, frozen, x_all, y_all, jnp.asarray(gidx),
                                      jnp.asarray(gw), jnp.int32(n_steps))
                upd.append(flat(u))
                if n_steps:
                    losses.append(float(loss))
            u_mat = jnp.stack(upd)                                  # (P, D) f32
            sel = sizes[ids].astype(np.float32)
            w_new = aggregate(w, jnp.asarray(sel / sel.sum(), jnp.float32), u_mat)
            if not explore:
                # Alg. 3: ordered pairs of negative cosine, sure and possible
                cos = np.asarray(cosines(u_mat), np.float64)
                off = ~np.eye(p, dtype=bool)
                rec["pairs"] = (int(np.sum((cos < -COS_BAND) & off)),
                                int(np.sum((cos < COS_BAND) & off)))
            t1 = time.perf_counter()

            # Alg. 1: write V/A/R for the whole cohort, then model each row
            slots = []
            for cid in ids:
                slot_of.setdefault(cid, len(slot_of))
                slots.append(slot_of[cid])
                slot_client[slot_of[cid]] = cid
                slot_last[slot_of[cid]] = t
            idx = jnp.asarray(slots, jnp.int32)
            vs = vs.at[idx].set(u_mat)
            as_ = as_.at[idx].set(jnp.broadcast_to(w, u_mat.shape))
            cos, eq6 = (np.asarray(a) for a in relations(u_mat, w, vs, as_))
            seen = slot_last >= 0
            fresh = slot_last >= t - 1
            for i, cid in enumerate(ids):
                row = np.where(fresh, cos[i], eq6[i])
                prev = omega[cid, cid]
                omega[cid, slot_client[seen]] = row[seen]
                omega[cid, cid] = prev
                # Eq. 7: H is the row's sum without the client itself
                heur[cid] = float(np.sum(omega[cid], dtype=np.float64) - omega[cid, cid])
                out["omega"][(t, cid)] = omega[cid].copy()
                out["heuristic"][(t, cid)] = heur[cid]
                out["updates"][(t, cid)] = np.asarray(u_mat[i])
            t2 = time.perf_counter()

            params = _unflatten_like(params0, w_new)
            w = w_new
            out["models"][t + 1] = np.asarray(w)
            rec["loss"] = float(np.mean(losses)) if losses else float("nan")
            rec["accuracy"] = self._accuracy(params, frozen, ex, ey)
            out["rounds"].append(rec)
            clock["train"] += t1 - t0
            clock["ingest"] += t2 - t1
            clock["eval"] += time.perf_counter() - t2
        out["seconds"] = clock
        return out


def _unflatten_like(tree, flat: jax.Array):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out, off = [], 0
    for leaf in leaves:
        n = int(np.prod(leaf.shape))
        out.append(flat[off:off + n].reshape(leaf.shape).astype(leaf.dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def leaf_sizes(tree) -> List[int]:
    return [int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree)]
