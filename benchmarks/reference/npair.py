"""Plain mined N-pair loss, its layer-defined backward, and Caffe SGD.

Written from the published semantics of the reference Caffe layer
``NPairMultiClassLoss`` (``npair_multi_class_loss.cu``) and of Caffe's
SGD solver; float32 ``jax.numpy``, imports nothing of the program.

G ranks each hold N rows.  Rank r scores its rows against the gathered
pool of all N*G rows, excludes the self pair, mines positives and
negatives against thresholds, and takes a stabilized masked softmax:

    loss_r = -(1/N) sum_q log(sum_sel_pos e^s / sum_sel e^s)

The objective is the mean over ranks.  The backward is the LAYER's, not
autodiff of the above: thresholds are constants, the query-role and the
database-role gradients are averaged 0.5/0.5, and the database role is
summed over ranks and divided by G.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
FLT_MAX = float(np.finfo(np.float32).max)
HARD, EASY, RAND, RELATIVE_HARD, RELATIVE_EASY = range(5)
GLOBAL, LOCAL = 0, 1
METHODS = {"HARD": HARD, "EASY": EASY, "RAND": RAND,
           "RELATIVE_HARD": RELATIVE_HARD, "RELATIVE_EASY": RELATIVE_EASY}
REGIONS = {"GLOBAL": GLOBAL, "LOCAL": LOCAL}


def _kth_largest(values, mask, sn, axis):
    """The ``sn``-th largest masked value (sn >= 0: an absolute rank from
    the top, clamped to the list), FLT_MAX for an empty list, and
    -FLT_MAX where the value found is negative (the layer's clamp)."""
    if not sn >= 0:  # -0.0 counts as 0, as in the layer's C comparison
        raise NotImplementedError("relative mining by fraction (sn < 0)")
    count = jnp.sum(mask, axis=axis)
    ordered = -jnp.sort(jnp.where(mask, -values, jnp.inf), axis=axis)
    idx = jnp.clip(jnp.minimum(int(sn), count - 1), 0, None)
    val = jnp.take_along_axis(ordered, jnp.expand_dims(idx, axis), axis)
    val = jnp.squeeze(val, axis)
    val = jnp.where(val >= 0, val, -FLT_MAX)
    return jnp.where(count > 0, val, FLT_MAX)


def rank_weights(f, labels, total_f, total_l, rank, mining):
    """One rank's loss and pair weights w = d loss / d sims, (N, N*G)."""
    n, ng = f.shape[0], total_f.shape[0]
    sims = jnp.matmul(f, total_f.T, precision=_HI)
    not_self = jnp.arange(ng)[None, :] != (rank * n + jnp.arange(n))[:, None]
    same = (labels[:, None] == total_l[None, :]) & not_self
    diff = (labels[:, None] != total_l[None, :]) & not_self
    max_all = jnp.max(jnp.where(same | diff, sims, -FLT_MAX), axis=1)
    min_within = jnp.min(jnp.where(same, sims, FLT_MAX), axis=1)
    max_between = jnp.max(jnp.where(diff, sims, -FLT_MAX), axis=1)
    ap_m, an_m = METHODS[mining["ap_mining_method"]], METHODS[mining["an_mining_method"]]
    ap_r, an_r = REGIONS[mining["ap_mining_region"]], REGIONS[mining["an_mining_region"]]
    relative = (RELATIVE_HARD, RELATIVE_EASY)
    flat = lambda m: (sims.reshape(-1), m.reshape(-1))
    if ap_m in relative:
        pos_thr = (_kth_largest(sims, same, mining["identsn"], 1) if ap_r == LOCAL
                   else _kth_largest(*flat(same), mining["identsn"], 0))
    else:
        pos_thr = max_between if ap_r == LOCAL else jnp.max(max_between)
    if an_m in relative:
        neg_thr = (_kth_largest(sims, diff, mining["diffsn"], 1) if an_r == LOCAL
                   else _kth_largest(*flat(diff), mining["diffsn"], 0))
    else:
        neg_thr = min_within if an_r == LOCAL else jnp.min(min_within)
    pt = jnp.broadcast_to(pos_thr, (n,))[:, None] + jnp.float32(mining["margin_ident"])
    nt = jnp.broadcast_to(neg_thr, (n,))[:, None] + jnp.float32(mining["margin_diff"])
    pick = lambda m, s, t: {HARD: s < t, EASY: s >= t, RAND: jnp.ones_like(s, bool),
                            RELATIVE_HARD: s <= t, RELATIVE_EASY: s >= t}[m]
    # negatives mirror the comparisons: HARD is s > t, EASY s <= t,
    # RELATIVE_HARD s >= t, RELATIVE_EASY s <= t.
    pick_neg = lambda m, s, t: {HARD: s > t, EASY: s <= t, RAND: jnp.ones_like(s, bool),
                                RELATIVE_HARD: s >= t, RELATIVE_EASY: s <= t}[m]
    sel_pos = same & pick(ap_m, sims, pt)
    sel_neg = diff & pick_neg(an_m, sims, nt)
    e = jnp.exp(sims - max_all[:, None])
    exp_pos, exp_neg = e * sel_pos, e * sel_neg
    ident = exp_pos.sum(1)
    both = ident + exp_neg.sum(1)
    ok = (ident != 0) & (both != 0)
    loss = -jnp.sum(jnp.where(ok, jnp.log(jnp.where(ok, ident / jnp.where(both != 0, both, 1.0), 1.0)), 0.0)) / n
    safe = lambda num, den: jnp.where(den[:, None] != 0, num / jnp.where(den != 0, den, 1.0)[:, None], 0.0)
    w = (-safe(exp_pos, ident) + safe(exp_pos, both) + safe(exp_neg, both)) / n
    return loss, w


def loss_and_embedding_grad(emb, labels, ranks, mining):
    """Objective (mean of the ranks' losses) and its layer-defined
    gradient with respect to the (N*G, D) embeddings."""
    ng = emb.shape[0]
    n = ng // ranks
    losses, query, db = [], [], jnp.zeros_like(emb)
    for r in range(ranks):
        f = emb[r * n:(r + 1) * n]
        loss, w = rank_weights(f, labels[r * n:(r + 1) * n], emb, labels, r, mining)
        losses.append(loss)
        query.append(jnp.matmul(w, emb, precision=_HI))
        db = db + jnp.matmul(w.T, f, precision=_HI)
    grad = 0.5 * (db / ranks) + 0.5 * jnp.concatenate(query, axis=0)
    return jnp.mean(jnp.stack(losses)), grad / ranks


def sgd_step(params, velocity, grads, lr, momentum, weight_decay):
    """Caffe SGD: v <- mu v + lr (g + wd w);  w <- w - v."""
    new_v = jax.tree_util.tree_map(
        lambda v, g, w: momentum * v + lr * (g + weight_decay * w),
        velocity, grads, params)
    new_p = jax.tree_util.tree_map(lambda w, v: w - v, params, new_v)
    return new_p, new_v


def caffe_lr(solver, step):
    if solver["lr_policy"] == "fixed":
        return solver["base_lr"]
    if solver["lr_policy"] == "step":
        return solver["base_lr"] * solver["gamma"] ** (step // solver["stepsize"])
    raise NotImplementedError(solver["lr_policy"])


class Trainer:
    """Follows training steps of the plain model in row blocks, so the
    float32 activations of one block are all the device holds.

    ``embed`` is ``embed(params, x, quant=...)``.  One step: embed the
    batch block by block, take the loss and the embedding gradient on
    the whole pool, pull each block's gradient back through the trunk
    (recomputing its forward), then one SGD update.
    """

    def __init__(self, embed, params, mining, solver, ranks=1, block=32,
                 quant=None, loss_fn=None):
        self.mining, self.solver, self.ranks = mining, solver, ranks
        self.block = block
        self.reset(params)
        fwd = lambda p, x: embed(p, x, quant=quant)
        self._fwd = jax.jit(fwd)

        def pull(p, x, ct, acc):
            _, vjp = jax.vjp(lambda pp: fwd(pp, x), p)
            return jax.tree_util.tree_map(jnp.add, acc, vjp(ct)[0])

        self._pull = jax.jit(pull, donate_argnums=(3,))
        self._loss = jax.jit(loss_fn or (
            lambda e, l: loss_and_embedding_grad(e, l, ranks, mining)))
        self._sgd = jax.jit(sgd_step, donate_argnums=(0, 1))

    def reset(self, params):
        """Start again from ``params`` (the compiled programs stay)."""
        self.params = jax.tree_util.tree_map(jnp.array, params)
        self.velocity = jax.tree_util.tree_map(jnp.zeros_like, self.params)
        self.step_count, self.first_grads = 0, None

    def step(self, images, labels):
        n = images.shape[0]
        blocks = [(i, min(i + self.block, n)) for i in range(0, n, self.block)]
        emb = jnp.concatenate(
            [self._fwd(self.params, jnp.asarray(images[a:b])) for a, b in blocks])
        loss, ct = self._loss(emb, jnp.asarray(labels))
        grads = jax.tree_util.tree_map(jnp.zeros_like, self.params)
        for a, b in blocks:
            grads = self._pull(self.params, jnp.asarray(images[a:b]), ct[a:b], grads)
        if self.first_grads is None:
            self.first_grads = jax.tree_util.tree_map(np.asarray, grads)
        lr = caffe_lr(self.solver, self.step_count)
        self.params, self.velocity = self._sgd(
            self.params, self.velocity, grads, jnp.float32(lr),
            jnp.float32(self.solver["momentum"]),
            jnp.float32(self.solver["weight_decay"]))
        self.step_count += 1
        return float(loss)
