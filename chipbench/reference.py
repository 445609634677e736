"""Plain reference of the federated round, and the comparison that
decides ``correct``.

The reference follows the recipe as the paper states it, in straight
``jax.numpy`` at float32 (``Precision.HIGHEST`` for every convolution
and matrix product), and imports nothing of the program:

1. the round's cohort: ``S`` clients drawn uniformly without
   replacement, and each client's minibatches drawn uniformly with
   replacement from its real rows, both from the round key
   ``fold_in(key(sim_seed), round)`` split three ways (cohort, batches,
   scenario) -- the draws the recipe names, made with ``jax.random``;
2. local SGD: ``steps`` steps of ``w <- w - lr * grad`` of the mean
   cross-entropy, for every client of the cohort;
3. the criteria (Ds: real rows; Ld: distinct label values over every
   position of the real rows, ``-1`` being no target; Md:
   ``1 / sqrt(||w_G - w_k|| + 1)``), each normalised over the cohort,
   and the prioritized score (paper Eq. 4) into weights (Eq. 3);
4. Algorithm-1 (when on): one candidate ``sum_k p_k w_k`` per priority
   order, each scored by its accuracy on every real test row, and the
   paper's acceptance rule (keep the current order if it does not
   regress, else the first order that does not, else the best);
5. the committed model and its accuracy on every real test row: the
   sum of the rows' scores (the model file's ``row_scores``, by default
   ``argmax == label``) over the number of rows.

Only the leaves of the initial model are trained, aggregated and
compared.  A model's shared weights (``init_shared``, see
``chipbench/harness.py``) are an argument of every jitted function here
and reach ``loss``, ``forward`` and ``row_scores`` as ``shared=``.

Run as the *judge*, it is handed the priority orders a system chose and
commits those, so that both follow one trajectory; the distance of each
choice from what the rule allows under the judge's own accuracies is
one of the numbers compared (``alg1_slack``).  It also evaluates the
system's own final model, to check the accuracy the system reports for
it (``eval_gap``).
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EVAL_BLOCK = 1024        # test rows per forward pass of an evaluation
TRAIN_ROWS = 2048        # rows per local step over all clients of a chunk


def cohort(sim_seed: int, rnd: int, num_clients: int, s: int,
           counts: np.ndarray, steps: int, batch: int):
    """Round ``rnd``'s cohort ``[S]`` and batch plans ``[S, steps, B]``."""
    key = jax.random.fold_in(jax.random.key(sim_seed), rnd)
    k_sel, k_batch, _ = jax.random.split(key, 3)
    sel = jnp.sort(jax.random.permutation(k_sel, num_clients)[:s])
    n = jnp.asarray(counts)[sel]
    keys = jax.random.split(k_batch, s)
    plans = jax.vmap(lambda k, c: jax.random.randint(
        k, (steps, batch), 0, jnp.maximum(c, 1), dtype=jnp.int32))(keys, n)
    return np.asarray(sel), plans


def choose(q: np.ndarray, prev_q: float, cur: int) -> int:
    """Algorithm-1's acceptance rule over the candidates' accuracies."""
    if q[cur] >= prev_q:
        return cur
    for j in range(len(q)):
        if j != cur and q[j] >= prev_q:
            return j
    return int(np.argmax(q))


def _bound(shared) -> dict:
    """The keyword a model function takes shared weights by, if any."""
    return {} if shared is None else {"shared": shared}


class Reference:
    """The federated round of one cell, computed plainly.

    ``dtype``/``precision`` are float32 / HIGHEST for the reference; the
    lower-precision control runs the same code one step below the
    configuration's precision (``chipbench.precision.control_of``).
    ``rule`` is Algorithm-1's acceptance rule.
    """

    rule = staticmethod(choose)

    def __init__(self, data, model, recipe: dict, dtype=jnp.float32,
                 precision=HIGHEST):
        self.data, self.model, self.r = data, model, recipe
        self.dtype, self.precision = dtype, precision
        self.perms = list(itertools.permutations(range(len(recipe["criteria"]))))
        d = data
        # real test rows only, padded to whole evaluation blocks (rows of
        # zeros, labels of -1, scored 0 whatever the model says)
        rows = np.concatenate([d.test_images[k, :d.test_counts[k]]
                               for k in range(d.num_clients)])
        labs = np.concatenate([d.test_labels[k, :d.test_counts[k]]
                               for k in range(d.num_clients)])
        self.n_test = len(labs)

        def blocks(a, fill):
            pad = [(0, (-len(a)) % EVAL_BLOCK)] + [(0, 0)] * (a.ndim - 1)
            a = np.pad(a, pad, constant_values=fill)
            return jnp.asarray(a).reshape(-1, EVAL_BLOCK, *a.shape[1:])

        self.t_x, self.t_y = blocks(rows, 0), blocks(labs, -1)
        self.t_real = blocks(np.ones(self.n_test, bool), False)
        self.distinct = np.array([self._distinct(d.labels[k, :d.counts[k]])
                                  for k in range(d.num_clients)], np.float32)
        self._train = jax.jit(self._train_cohort)
        self._evaluate = jax.jit(jax.vmap(self._accuracy, in_axes=(0, None)))
        self._norms = jax.jit(lambda w, g: jnp.sqrt(sum(
            jnp.sum(jnp.square((w[k] - g[k][None]).astype(jnp.float32)),
                    axis=tuple(range(1, w[k].ndim))) for k in w)))
        self._aggregate = jax.jit(lambda w, p: {
            k: jnp.sum(p.reshape((-1,) + (1,) * (v.ndim - 1))
                       * v.astype(jnp.float32), axis=0).astype(v.dtype)
            for k, v in w.items()})

    @staticmethod
    def _distinct(labels: np.ndarray) -> int:
        values = np.unique(labels)
        return int(np.sum(values >= 0))

    # -- local training ------------------------------------------------
    def _train_cohort(self, params, rows, labels, plans, shared):
        lr = jnp.asarray(self.r["lr"], self.dtype)
        prec, kw = self.precision, _bound(shared)

        def one(xs):
            x, y, plan = xs

            def step(w, idx):
                g = jax.grad(self.model.loss)(w, x[idx], y[idx], prec, **kw)
                return {k: w[k] - lr * g[k].astype(self.dtype) for k in w}, None

            w, _ = jax.lax.scan(step, params, plan)
            return w

        chunk = max(1, TRAIN_ROWS // self.r["batch_size"])
        return jax.lax.map(one, (rows, labels, plans), batch_size=chunk)

    # -- evaluation -----------------------------------------------------
    def row_scores(self, params, x, y, shared):
        """``[B]`` scores in [0, 1]: the model file's ``row_scores``, or
        whether the row's label is the argmax of the logits."""
        kw = _bound(shared)
        if hasattr(self.model, "row_scores"):
            return self.model.row_scores(params, x, y, self.precision, **kw)
        logits = self.model.forward(params, x, self.precision, **kw)
        return jnp.argmax(logits, axis=-1) == y

    def _accuracy(self, params, shared):
        def block(_, xyr):
            x, y, real = xyr
            scores = self.row_scores(params, x, y, shared)
            return None, jnp.sum(jnp.where(real, scores, 0).astype(
                jnp.float32))

        _, hits = jax.lax.scan(block, None, (self.t_x, self.t_y, self.t_real))
        return jnp.sum(hits)

    def accuracy(self, cands: List[dict], shared=None) -> np.ndarray:
        stacked = {k: jnp.stack([c[k] for c in cands]) for k in cands[0]}
        hits = self._evaluate(stacked, shared)
        return np.asarray(hits, np.float64) / self.n_test

    # -- weights ----------------------------------------------------------
    @staticmethod
    def prioritized_weights(c: np.ndarray, perm) -> np.ndarray:
        ordered = c[:, list(perm)]
        lam = np.concatenate([np.ones_like(ordered[:, :1]),
                              np.cumprod(ordered[:, :-1], axis=1)], axis=1)
        s = np.sum(lam * ordered, axis=1)
        return s / s.sum()

    def criteria(self, sel, stacked, params) -> np.ndarray:
        cols = []
        for name in self.r["criteria"]:
            if name == "Ds":
                v = self.data.counts[sel].astype(np.float64)
            elif name == "Ld":
                v = self.distinct[sel].astype(np.float64)
            elif name == "Md":
                v = 1.0 / np.sqrt(np.asarray(self._norms(stacked, params),
                                             np.float64) + 1.0)
            else:
                raise KeyError(f"criterion {name!r} has no reference")
            cols.append(v / v.sum())
        return np.stack(cols, axis=1)

    # -- the rounds -------------------------------------------------------
    def run(self, params0: dict, rounds: int,
            forced: Optional[List[int]] = None,
            shared=None) -> Dict[str, object]:
        """``rounds`` rounds from ``params0``, under the model's ``shared``
        weights (``None`` where it has none); with ``forced``, commit the
        given priority orders (as indices into the permutations)."""
        r, d = self.r, self.data
        params = {k: jnp.asarray(v, self.dtype) for k, v in params0.items()}
        cur = self.perms.index(tuple(r["priority"]))
        prev_q = 0.0
        out = {"acc": [], "priority": [], "entropy": [], "slack": []}
        for rnd in range(1, rounds + 1):
            sel, plans = cohort(r["sim_seed"], rnd, d.num_clients, r["S"],
                                d.counts, r["steps"], r["batch_size"])
            stacked = self._train(params, jnp.asarray(d.images[sel]),
                                  jnp.asarray(d.labels[sel]), plans, shared)
            c = self.criteria(sel, stacked, params)
            if r["online_adjust"]:
                ws = [self.prioritized_weights(c, p) for p in self.perms]
                cands = [self._aggregate(stacked, jnp.asarray(w, jnp.float32))
                         for w in ws]
                q = self.accuracy(cands, shared)
                own = self.rule(q, prev_q, cur)
                pick = own if forced is None else forced[rnd - 1]
                out["slack"].append(slack(q, prev_q, cur, pick))
                params, p, acc = cands[pick], ws[pick], float(q[pick])
                cur, prev_q = pick, acc
            else:
                p = self.prioritized_weights(c, tuple(r["priority"]))
                params = self._aggregate(stacked, jnp.asarray(p, jnp.float32))
                acc = float(self.accuracy([params], shared)[0])
            out["acc"].append(acc)
            out["priority"].append(cur)
            out["entropy"].append(float(-np.sum(p * np.log(np.maximum(p, 1e-12)))))
        out["params"] = {k: np.asarray(v, np.float32) for k, v in params.items()}
        return out


    def check(self, observed: dict, w0: dict,
              shared=None) -> Dict[str, float]:
        """Follow the checked rounds forced onto ``observed``'s priority
        orders, evaluate ``observed``'s final model, and compare."""
        judged = self.run(w0, len(observed["acc"]), forced=observed["priority"],
                          shared=shared)
        final = {k: jnp.asarray(v, self.dtype)
                 for k, v in observed["params"].items()}
        evaluated = float(self.accuracy([final], shared)[0])
        return compare(observed, judged, w0, self.r["online_adjust"],
                       evaluated)


def slack(q: np.ndarray, prev_q: float, cur: int, pick: int) -> float:
    """How far the accuracies ``q`` must move for the rule to pick ``pick``
    (0 where it does): as the current order that does not regress, as the
    first other order that does not, or as the best where every order
    regresses (the current one included)."""
    others = [j for j in range(len(q)) if j != cur]
    best = max([q[j] - prev_q for j in others] + [q.max() - q[pick], 0.0])
    if pick == cur:
        return float(min(max(0.0, prev_q - q[cur]), best))
    keep = max(0.0, q[cur] - prev_q)
    first = max([prev_q - q[pick]] + [q[j] - prev_q for j in others
                                      if j < pick] + [0.0])
    return float(max(keep, min(first, best)))


def leaf_gaps(w0: dict, sys_w: dict, ref_w: dict) -> Dict[str, float]:
    """Worst-leaf gaps of the model's change over the checked rounds.

    For each leaf, the change ``w - w0`` of the system and of the
    reference: ``change_gap`` compares their norms, ``change_diff`` is
    the norm of their difference, both over the larger of the
    reference's change of that leaf and of the median leaf.  Leaves the
    reference moves by under a thousandth of the median leaf are left
    out (nothing there but rounding).  A NaN on either side reads NaN.
    """
    ref_n, gap, diff = {}, {}, {}
    for k in ref_w:
        dr = ref_w[k].astype(np.float64) - w0[k]
        ds = np.asarray(sys_w[k], np.float64) - w0[k]
        ref_n[k] = np.linalg.norm(dr)
        gap[k] = abs(np.linalg.norm(ds) - ref_n[k])
        diff[k] = np.linalg.norm(ds - dr)
    med = float(np.median(list(ref_n.values())))
    keep = [k for k in ref_n if not ref_n[k] < 1e-3 * med]
    return {
        "change_gap": worst(gap[k] / np.maximum(ref_n[k], med) for k in keep),
        "change_diff": worst(diff[k] / np.maximum(ref_n[k], med)
                             for k in keep),
    }


def worst(values) -> float:
    """The largest of ``values``; NaN where any is."""
    return float(np.max(list(values)))


def compare(observed: dict, judged: dict, w0: dict, online_adjust: bool,
            evaluated: float) -> Dict[str, float]:
    """The numbers that decide ``correct``: ``observed`` is what the system
    under test produced over the checked rounds, ``judged`` the reference
    run forced onto its priority orders, ``evaluated`` the reference's
    accuracy of the system's own final model."""
    nums = {
        "acc_gap": worst(abs(a - b) for a, b in zip(observed["acc"],
                                                    judged["acc"])),
        "eval_gap": abs(observed["acc"][-1] - evaluated),
        "entropy_gap": worst(abs(a - b) for a, b in zip(observed["entropy"],
                                                        judged["entropy"])),
    }
    if online_adjust:
        nums["alg1_slack"] = worst(judged["slack"])
    nums.update(leaf_gaps(w0, observed["params"], judged["params"]))
    return {k: float(v) for k, v in nums.items()}
