"""Plain reference of a federated round: masked local Adam and
participation-weighted FedAvg, in straightforward ``jax.numpy``.

It imports nothing of the program.  It is given the initial weights
(made by the benchmark from the seed), the rows each client was fed and
the selection rows the program drew, and follows the rounds from there:

* each client trains the units its selection row marks, with a fresh
  Adam (b1 0.9, b2 0.999, eps 1e-8) per round, one step per local batch;
  a frozen unit keeps its weights exactly;
* a client's round loss is the mean of its step losses, each taken
  before the step;
* each unit's new value is its old value plus the weighted mean of the
  deltas of the clients that trained it; a unit nobody trained keeps its
  value.

``dtype`` float32 at ``precision`` "highest" is the reference; bfloat16
at the default precision runs every array in bfloat16 (the control: the
reference in the precision below the configuration's).  ``fault`` plants one of the
faults the comparison must catch in place of the program:
``"half_batch"`` trains and scores every client on the first half of
each batch, ``"half_cohort"`` leaves the second half of the cohort out
of the average (the mean is taken over the rest), ``"own_shard"`` keeps
only the first of ``shards`` equal parts of the cohort (what one chip
holds when the exchange between chips is left out), ``"flip_client"``
negates the first client's delta before the average.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


def _client_round(loss: Callable, lr: float, params, sel_row, batches,
                  units: Sequence[str]):
    """One client's local round: (delta, mean step loss)."""
    def step(carry, batch):
        p, m, v, t = carry
        value, g = jax.value_and_grad(loss)(p, batch)
        t = t + 1
        tf = t.astype(jnp.float32)
        c1 = 1.0 - B1 ** tf
        c2 = 1.0 - B2 ** tf
        np_, nm, nv = {}, {}, {}
        for i, u in enumerate(units):
            k = sel_row[i] > 0

            def upd(pp, gg, mm, vv):
                m2 = B1 * mm + (1 - B1) * gg
                v2 = B2 * vv + (1 - B2) * gg * gg
                step_ = lr * (m2 / c1.astype(m2.dtype)) / (
                    jnp.sqrt(v2 / c2.astype(v2.dtype)) + EPS)
                return (jnp.where(k, pp - step_.astype(pp.dtype), pp),
                        jnp.where(k, m2, mm), jnp.where(k, v2, vv))

            out = jax.tree_util.tree_map(upd, p[u], g[u], m[u], v[u])
            is_t = lambda x: isinstance(x, tuple)
            np_[u] = jax.tree_util.tree_map(lambda o: o[0], out, is_leaf=is_t)
            nm[u] = jax.tree_util.tree_map(lambda o: o[1], out, is_leaf=is_t)
            nv[u] = jax.tree_util.tree_map(lambda o: o[2], out, is_leaf=is_t)
        return (np_, nm, nv, t), value

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    (p, _, _, _), losses = jax.lax.scan(
        step, (params, zeros, zeros, jnp.zeros((), jnp.int32)), batches)
    delta = jax.tree_util.tree_map(lambda a, b: a - b, p, params)
    return delta, jnp.mean(losses.astype(jnp.float32))


def run_rounds(loss: Callable, params0, feed: List[Dict], units: Sequence[str],
               lr: float, *, chunk: int, dtype=jnp.float32,
               precision: Optional[str] = "highest",
               fault: Optional[str] = None, shards: int = 1) -> Dict:
    """Follow the recorded rounds from ``params0``.

    ``feed`` holds one entry per round: ``sel (C, U)``, ``weights (C,)``
    and ``chunks``, a list of client-stacked batch dicts with leaves
    ``(k, steps, B, ...)`` in cohort order.  Returns the per-round
    per-client losses, the params after round 1 and after the last round
    (float64 host copies), and the per-leaf norm of the mean first-step
    gradient at ``params0`` over the first round's clients.
    """
    prec = jax.default_matmul_precision(precision) if precision \
        else contextlib.nullcontext()
    with prec:
        cast = lambda t: jax.tree_util.tree_map(
            lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
            else x, t)
        p = cast(jax.tree_util.tree_map(jnp.asarray, params0))

        def many(gp, sel, batches):
            return jax.vmap(lambda s, b: _client_round(loss, lr, gp, s, b,
                                                       units))(sel, batches)

        many = jax.jit(many)
        # the first step's gradient at params0, summed over clients
        grad_sum = jax.jit(lambda gp, b: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32).sum(0),
            jax.vmap(jax.grad(loss), in_axes=(None, 0))(gp, b)))
        out = {"losses": [], "params": []}
        g_sum, g_n = None, 0
        for r, rnd in enumerate(feed):
            sel = np.asarray(rnd["sel"], np.float32)
            w = np.asarray(rnd["weights"], np.float32)
            num = jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, jnp.float32), p)
            den = np.zeros(len(units), np.float64)
            losses = []
            lo = 0
            for batches in rnd["chunks"]:
                k = len(next(iter(jax.tree_util.tree_leaves(batches))))
                for a in range(0, k, chunk):
                    b = jax.tree_util.tree_map(
                        lambda x: jnp.asarray(x[a:a + chunk]), batches)
                    if fault == "half_batch":
                        b = jax.tree_util.tree_map(
                            lambda x: x[:, :, : x.shape[2] // 2], b)
                    b = cast(b)
                    n = len(next(iter(jax.tree_util.tree_leaves(b))))
                    s = sel[lo + a: lo + a + n]
                    ws = w[lo + a: lo + a + n]
                    if fault in ("half_cohort", "own_shard"):
                        kept = len(w) // (2 if fault == "half_cohort"
                                          else shards)
                        ws = np.where(np.arange(lo + a, lo + a + n) < kept,
                                      ws, 0.0)
                    d, ls = many(p, jnp.asarray(s), b)
                    losses.append(np.asarray(ls, np.float64))
                    if r == 0:
                        g = grad_sum(p, jax.tree_util.tree_map(
                            lambda x: x[:, 0], b))
                        g_sum = g if g_sum is None else \
                            jax.tree_util.tree_map(jnp.add, g_sum, g)
                        g_n += n
                    sign = np.ones(n, np.float32)
                    if fault == "flip_client" and lo + a == 0:
                        sign[0] = -1.0
                    for i, u in enumerate(units):
                        wu = jnp.asarray(s[:, i] * ws * sign)
                        num[u] = jax.tree_util.tree_map(
                            lambda acc, dd: acc + jnp.tensordot(
                                wu, dd.astype(jnp.float32), axes=(0, 0)),
                            num[u], d[u])
                        den[i] += float(np.sum(s[:, i] * ws))
                lo += k
            for i, u in enumerate(units):
                if den[i] > 0:
                    p[u] = jax.tree_util.tree_map(
                        lambda x, acc: (x.astype(jnp.float32)
                                        + acc / den[i]).astype(x.dtype),
                        p[u], num[u])
            out["losses"].append(np.concatenate(losses))
            if r == 0 or r == len(feed) - 1:
                out["params"].append(host(p))
        out["grad0_norms"] = leaf_norms(
            jax.tree_util.tree_map(lambda x: np.asarray(x / g_n, np.float64),
                                   g_sum))
    out["p1"], out["p_last"] = out["params"][0], out["params"][-1]
    return out


def host(tree) -> Dict[str, np.ndarray]:
    """Flat ``{leaf path: float64 array}`` host copy."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): np.asarray(v, np.float64)
            for k, v in flat}


def leaf_norms(tree) -> Dict[str, float]:
    return {k: float(np.linalg.norm(v.ravel())) for k, v in host(tree).items()}
