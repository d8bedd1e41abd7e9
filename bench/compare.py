"""The comparison that decides ``correct``: the program against the
reference, number by number, each held to its limit.

What the program shows after the job:

* its round records: the cohort, the exploit flag, the mean client loss
  and the eval accuracy of every round;
* FLrce's server state, which keeps for each client its latest update
  (V), the global model it left from (A), the round (R), its relationship
  row (Omega) and heuristic (H), and Alg. 3's conflict average of the last
  round.  A client last active in one of the first rounds still holds that
  round's values at the end of the job; the last round's cohort holds the
  window's last round;
* the final global model, and the round at which Alg. 3 would first have
  stopped the job.

The reference follows the job from its seed through its first
``check_rounds`` rounds (``bench/reference.py``), exploit rounds included.
The window's last round is checked against a plain recomputation from the
state that round started from: Alg. 1's rows (Eq. 5 and 6 in float64 from
V, A and R), Alg. 3's count (from the cohort's V rows) and Eq. 4 (the
cohort's V rows, weighted by client size, added to its anchor).

Numbers (each the worst over what the run shows):

``selection``  rounds whose cohort or exploit flag differ (exact, limit 0);
               an exploit round whose cohort differs ends the rounds
               followed, and counts only where the reference's margin
               between its P-th and (P+1)-th H exceeds twice the
               ``heuristic`` limit
``es``         Alg. 3's stop in a round followed: 1 where the program's
               first stop round and the reference's disagree, where the
               reference's pair count decides it (exact, limit 0)
``loss0``      relative gap of round 0's mean client loss (read, not
               compared)
``loss``       relative gap of the mean client loss of a round after the
               first.  Round 0 starts from random weights, where a client
               of a few samples can take steps at a saturated softmax whose
               losses rounding decides: on one TPU v5e, at one bfloat16
               pass (the configuration's precision), a client of 2 samples
               read a mean step loss of 11.1 where the float32 reference
               reads 1.04, and its round's mean loss 38.6% off.  From round
               1 on, every client starts from the aggregate and the gap is
               steady from seed to seed
``accuracy``   gap of a round's eval accuracy
``update0``    gap of a round-0 client update's norm, by the worst leaf
               (read, not compared)
``update``     the same gap of a client of a round after the first.  Round
               0 starts from random weights, where a client of a few
               samples takes its steps at a saturated softmax and one
               bfloat16 pass (the configuration's precision) sets its
               update: on one TPU v5e a 2-sample client's gap read 0.447
               where every other client compared read 0.042 or less.  From
               round 1 on, every client starts from the aggregate
``model``      gap of the norm of the global model's change since the
               start, by the worst leaf
``omega0``     gap of a relationship degree in a round-0 client's row
               (read, not compared: it holds that client's update)
``omega``      the same in the row of a client of a round after the first
``heuristic``  gap of a client's H
``conflicts``  the window's last round: Alg. 3's ordered conflicting pairs
               outside the range that the recount allows (cosines within
               ``COS_BAND`` of 0 may count either way); 0 pairs in an
               explore round (exact, limit 0)
``aggregate``  the window's last round: Eq. 4, the gap of the norm of the
               global model's change in that round, by the worst leaf
``omega_last`` the window's last round: gap of a relationship degree
               against the float64 recomputation

A leaf's gap is ``|n_prog - n_ref| / max(n_ref, median leaf n_ref)``.
Leaves whose reference norm is under a thousandth of the median leaf's
are left out (they move by round-off alone); where most leaves did not
move at all, the largest leaf stands in for the median.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

from reference import COS_BAND

NUMBERS = ("selection", "es", "loss0", "loss", "accuracy", "update0", "update", "model",
           "omega0", "omega", "heuristic", "conflicts", "aggregate", "omega_last")


def leaf_gap(prog: np.ndarray, ref: np.ndarray, sizes: List[int]) -> float:
    cuts = np.cumsum(sizes)[:-1]
    n_prog = np.asarray([np.linalg.norm(a) for a in np.split(prog.astype(np.float64), cuts)])
    n_ref = np.asarray([np.linalg.norm(a) for a in np.split(ref.astype(np.float64), cuts)])
    med = float(np.median(n_ref)) or float(np.max(n_ref))
    keep = n_ref >= 1e-3 * med
    if med <= 0 or not keep.any():
        return math.inf if np.any(n_prog > 0) else 0.0
    gap = np.abs(n_prog - n_ref) / np.maximum(n_ref, med)
    return float(np.max(gap[keep]))


def es_threshold(psi: float, p: int) -> int:
    """Alg. 3 stops where the ordered pairs reach ``psi * p``: the least
    whole count that does, resolved in float64."""
    n0 = max(0, math.ceil(psi * p))
    while n0 > 0 and (n0 - 1) / p >= psi:
        n0 -= 1
    while n0 / p < psi:
        n0 += 1
    return n0


def followed_rounds(pr: List[Dict], rr: List[Dict], h_limit: Optional[float]):
    """How many rounds the two sides share, and how many of those differ
    in their cohort or flag: a round whose flag differs, or whose explore
    draw differs, is a mismatch; an exploit round whose cohort differs
    ends what is followed, and is a mismatch only where the reference's
    top-P margin is wider than rounding of H on both sides can close (with
    no limit on H, never)."""
    bad, n = 0, 0
    for a, b in zip(pr, rr):
        if a["exploited"] != b["exploited"]:
            bad += 1
            break
        if a["ids"] != b["ids"]:
            if not b["exploited"] or (h_limit is not None and b["margin"] > 2.0 * h_limit):
                bad += 1
            break
        n += 1
    return n, bad + abs(len(pr) - len(rr))


def numbers(prog: Dict, ref: Dict, sizes: List[int], rounds: int,
            limits: Dict[str, float], psi: float) -> Dict[str, Optional[float]]:
    """Both sides are dicts of the same form (``run.observe`` for the
    program, :meth:`Reference.run` for the reference): ``rounds`` (ids,
    exploited, loss, accuracy per round), ``updates``, ``omega`` and
    ``heuristic`` keyed by ``(round, client)``, ``models`` keyed by the
    rounds done, and, for the program, ``es_stop_round`` and ``last`` (the
    window's last round, read by :func:`window_numbers`)."""
    out: Dict[str, Optional[float]] = {}
    rr = ref["rounds"][:rounds]
    pr = prog["rounds"][:len(rr)]
    followed, bad = followed_rounds(pr, rr, limits.get("heuristic"))
    out["selection"] = float(bad)
    # Alg. 3 over the rounds followed: the reference's first sure stop, or
    # the first round whose pair count rounding may put either side of it
    n0 = es_threshold(psi, len(rr[0]["ids"]))
    stop = prog.get("es_stop_round")
    prog_stop = stop if stop is not None and 0 <= stop < followed else None
    es = 0.0
    for t, b in enumerate(rr[:followed]):
        lo, hi = b.get("pairs", (0, 0))
        if lo >= n0:                              # the reference surely stops
            es = float(prog_stop != t)
            break
        if hi >= n0:                              # either may be right
            es = float(prog_stop is not None and prog_stop != t)
            break
    else:
        es = float(prog_stop is not None)
    out["es"] = es
    if followed == 0:
        for k in ("loss0", "loss", "accuracy", "update0", "update", "model", "omega0",
                  "omega", "heuristic"):
            out[k] = None
        return out
    pr, rr = pr[:followed], rr[:followed]
    loss_gaps = [abs(a["loss"] - b["loss"]) / abs(b["loss"]) for a, b in zip(pr, rr)]
    out["loss0"] = loss_gaps[0]
    out["loss"] = max(loss_gaps[1:]) if followed > 1 else None
    out["accuracy"] = max(abs(a["accuracy"] - b["accuracy"]) for a, b in zip(pr, rr))

    def worst(got: Dict, want: Dict, gap) -> Optional[float]:
        vals = [math.inf if k not in want else gap(v, want[k]) for k, v in got.items()]
        return max(vals) if vals else None

    w0 = ref["models"][0]
    gaps = client_gaps(prog, ref, sizes, followed)
    for name, part in (("update0", "update"), ("omega0", "omega")):
        out[name] = max((g[part] for g in gaps if g["round"] == 0), default=None)
        out[part] = max((g[part] for g in gaps if g["round"] >= 1), default=None)
    out["model"] = worst({t: v for t, v in prog["models"].items() if 1 <= t <= followed},
                         ref["models"], lambda a, b: leaf_gap(a - w0, b - w0, sizes))
    seen = lambda d: {k: v for k, v in d.items() if k[0] < followed}
    out["heuristic"] = worst(seen(prog["heuristic"]), ref["heuristic"],
                             lambda a, b: abs(float(a) - float(b)))
    return out


def client_gaps(prog: Dict, ref: Dict, sizes: List[int], followed: int) -> List[Dict]:
    """Per client the program shows from the rounds followed, by
    ``(round, client)``: its update's worst-leaf gap, its Omega row's
    widest gap and the client that degree is to.  A key the reference
    lacks reads inf."""
    out = []
    for key in sorted(k for k in prog["updates"] if k[0] < followed):
        t, c = key
        g = {"round": int(t), "client": int(c)}
        if key not in ref["updates"] or key not in ref["omega"] or key not in prog["omega"]:
            out.append({**g, "update": math.inf, "omega": math.inf, "widest_to": -1})
            continue
        d = np.abs(np.asarray(prog["omega"][key], np.float64) - ref["omega"][key])
        out.append({**g, "update": leaf_gap(prog["updates"][key], ref["updates"][key], sizes),
                    "omega": float(np.max(d)), "widest_to": int(np.argmax(d))})
    return out


def _orthdist(rel: np.ndarray, v: np.ndarray, vv: np.ndarray) -> np.ndarray:
    coef = np.einsum("md,md->m", rel, v) / vv
    return np.linalg.norm(rel - coef[:, None] * v, axis=1)


def relation_rows(ids: List[int], t: int, v: np.ndarray, a: np.ndarray,
                  last: np.ndarray) -> np.ndarray:
    """Alg. 1's rows for the cohort ``ids`` of round ``t`` in float64, from
    the maps as that round left them (the cohort's own rows written): Eq. 5
    (cosine) against peers active since round ``t - 1``, Eq. 6 (1 - the
    peer ray's orthogonal distance after the update over the one before)
    against older ones.  Entries for peers never seen are NaN."""
    v = v.astype(np.float64)
    vv = np.maximum(np.einsum("md,md->m", v, v), 1e-12)
    fresh, seen = last >= t - 1, last >= 0
    rows = np.full((len(ids), v.shape[0]), np.nan)
    anchor = None
    for i, c in enumerate(ids):
        u = v[c]
        if anchor is None or not np.array_equal(a[c], anchor):
            anchor = a[c]                          # the cohort's, as a rule
            rel = anchor.astype(np.float64)[None, :] - a
            before = _orthdist(rel, v, vv)
        cos = (v @ u) / np.maximum(np.sqrt(vv) * np.linalg.norm(u), 1e-12)
        after = _orthdist(rel + u[None, :], v, vv)
        eq6 = np.clip(1.0 - after / np.maximum(before, 1e-12), -1.0, 1.0)
        rows[i] = np.where(seen, np.where(fresh, cos, eq6), np.nan)
    return rows


def window_numbers(last: Dict, sizes: List[int], client_sizes: np.ndarray,
                   psi: float) -> Dict[str, Optional[float]]:
    """The window's last round against a recomputation from the state it
    started from.  ``last``: the round ``t``, its cohort ``ids`` and flag
    ``exploited``, the V and A maps and the R map at the end of the job, the
    cohort's Omega rows, Alg. 3's ``conflicts`` average and the final model
    ``w``."""
    ids, t = last["ids"], last["t"]
    v, a = last["v"], last["a"]
    out: Dict[str, Optional[float]] = {}
    p = len(ids)
    pairs = int(round(float(last["conflicts"]) * p))
    if last["exploited"]:
        u = v[ids].astype(np.float64)
        n = np.maximum(np.linalg.norm(u, axis=1), 1e-12)
        cos = (u @ u.T) / (n[:, None] * n[None, :])
        off = ~np.eye(p, dtype=bool)
        lo = int(np.sum((cos < -COS_BAND) & off))
        hi = int(np.sum((cos < COS_BAND) & off))
        out["conflicts"] = float(max(0, lo - pairs, pairs - hi))
    else:
        out["conflicts"] = float(abs(pairs))
    anchors = a[ids].astype(np.float64)
    if not np.all(anchors == anchors[0]):
        out["aggregate"] = math.inf                 # one round, one anchor
    else:
        wt = client_sizes[ids].astype(np.float64)
        want = (wt / wt.sum()) @ v[ids].astype(np.float64)
        out["aggregate"] = leaf_gap(last["w"].astype(np.float64) - anchors[0], want, sizes)
    rows = relation_rows(ids, t, v, a, last["r"])
    got = np.asarray(last["omega"], np.float64)
    mask = ~np.isnan(rows)
    mask[np.arange(p), ids] = False                 # Omega[k, k] is not a relation
    out["omega_last"] = float(np.max(np.abs(got - np.nan_to_num(rows))[mask])) if mask.any() else None
    return out


def judge(nums: Dict[str, Optional[float]], limits: Dict[str, float]) -> bool:
    """Correct when every number read is finite and within its limit."""
    for name, value in nums.items():
        if value is None or name not in limits:
            continue
        if not (math.isfinite(value) and value <= float(limits[name])):
            return False
    return True


def report(nums: Dict[str, Optional[float]], limits: Dict[str, float]) -> Dict:
    """Each number beside its limit, for the result line and stderr."""
    return {name: {"value": nums.get(name), "limit": limits.get(name)} for name in NUMBERS}
