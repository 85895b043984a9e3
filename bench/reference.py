"""Plain reference of the benchmarked training step.

Written from the equations, in straightforward ``jax.numpy``, and
independent of the program under test: nothing here imports ``repro``.
It covers every layer the timed step passes through:

* the model's forward pass and loss, from the configuration's family
  (``bench/families/<family>.py``: its ``param_shapes`` and ``loss``)
  and, through ``jax.grad``, its backward pass;
* Gaussian-k error feedback, paper Eq. (2): ``u = g + e``, the
  threshold of Algorithm 1 with its refinement band, the first
  ``k_cap`` coordinates over the threshold in index order, and the new
  residual ``e' = u - selected``;
* the exchange: the mean of every worker's selected vector;
* momentum SGD, ``m = mu * m + agg``, ``p = p - lr * m``.

``dtype=float32`` runs at ``highest`` matmul precision: the reference.
``dtype=bfloat16`` keeps weights, activations, residual and momentum in
bfloat16: the control, which the comparison must refuse.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.stats import norm

# ---------------------------------------------------------------------------
# weights, in the layout the program's step takes
# ---------------------------------------------------------------------------


def period(model: dict) -> int:
    return math.lcm(len(model["block_pattern"]), len(model["ffn_pattern"]))


def layer_sig(model: dict, layer: int):
    bp, fp = model["block_pattern"], model["ffn_pattern"]
    return bp[layer % len(bp)], fp[layer % len(fp)]


def pattern_shapes(model: dict, block_shapes) -> dict:
    """The program's tree of parameter shapes for a cycled layer pattern:
    layer kinds of one pattern period stacked over its repetitions, the
    remainder unstacked.  ``block_shapes(model, kind, ffn)`` gives one
    layer's shapes; a family gives its ``param_shapes`` through it."""
    D, V, L = model["d_model"], model["vocab_size"], model["num_layers"]
    P = period(model)
    reps, tail = divmod(L, P)
    stack = []
    for pos in range(P if reps else 0):
        one = block_shapes(model, *layer_sig(model, pos))
        stack.append(jax.tree.map(lambda s: (reps,) + s, one,
                                  is_leaf=lambda s: isinstance(s, tuple)))
    return {"embed": (V, D), "final_norm": {"scale": (D,)},
            "lm_head": (D, V), "stack": stack,
            "tail": [block_shapes(model, *layer_sig(model, reps * P + i))
                     for i in range(tail)]}


def leaf_paths(shapes) -> tuple:
    """(names, shapes) of the leaves of a tree of shapes, in the order
    the tree flattens; a name joins the path with '/', as
    ``stack/0/core/wq``."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in flat], \
        [shape for _, shape in flat]


def leaf_shapes(family, model: dict) -> tuple:
    """``leaf_paths`` of the family's parameter shapes for ``model``."""
    return leaf_paths(family.param_shapes(model))


def _leaf_init(path, shape, key):
    """Initial value of one leaf: ones for norm scales, 0 for biases,
    N(0, 1) for the embedding and N(0, 1/fan_in) for every weight
    matrix, fan_in being the second-to-last dim."""
    name = str(getattr(path[-1], "key", ""))
    if name == "scale":
        return jnp.ones(shape, jnp.float32)
    if len(shape) - (1 if str(getattr(path[0], "key", "")) == "stack"
                     else 0) <= 1:
        return jnp.zeros(shape, jnp.float32)
    std = 1.0 if name == "embed" else 1.0 / math.sqrt(shape[-2])
    return std * jax.random.normal(key, shape, jnp.float32)


def init_params(shapes, key) -> dict:
    """The weights of a tree of shapes (tuples), from ``key`` alone.
    Jit it with the shapes bound."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    leaves = [_leaf_init(path, shape, jax.random.fold_in(key, i))
              for i, (path, shape) in enumerate(flat)]
    return jax.tree.unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# the data: tokens of a seeded affine recurrence with sparse noise
# ---------------------------------------------------------------------------


def lm_batch(step: int, *, global_batch: int, seq_len: int, vocab: int,
             seed: int):
    """``t[i+1] = (31 t[i] + 7 + noise) mod vocab`` with a Bernoulli(0.1)
    noise draw per position; tokens are positions 0..S-1, labels 1..S."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), jnp.uint32(step))
    k1, k2, k3 = jax.random.split(key, 3)
    start = jax.random.randint(k1, (global_batch, 1), 0, vocab)
    noise = (jax.random.bernoulli(k2, 0.1, (global_batch, seq_len + 1))
             * jax.random.randint(k3, (global_batch, seq_len + 1), 0, vocab))
    # written as a loop on the host for clarity; S is at most a few
    # thousand and this runs only for the check
    noise = np.asarray(noise, np.int64)
    cur = np.asarray(start[:, 0], np.int64)
    out = np.empty((global_batch, seq_len + 1), np.int64)
    for i in range(seq_len + 1):
        cur = (cur * (31 % vocab) + 7 + noise[:, i]) % vocab
        out[:, i] = cur
    return {"tokens": out[:, :-1].astype(np.int32),
            "labels": out[:, 1:].astype(np.int32)}


# ---------------------------------------------------------------------------
# Gaussian-k error feedback (paper Eq. 2, Algorithm 1)
# ---------------------------------------------------------------------------


def gaussiank_budget(size: int, ratio: float):
    """(k, k_cap): k = max(1, ceil(ratio d)), capacity ceil(4k/3)."""
    k = min(size, max(1, math.ceil(ratio * size)))
    return k, min(size, math.ceil(4.0 * k / 3.0))


def gaussiank_ef(u, k: int, k_cap: int, refine_iters: int = 4):
    """Selected vector (dense, zeros off the selection) and new residual
    of one flat vector ``u = g + e``."""
    d = u.shape[0]
    x = u.astype(jnp.float32)
    mu, sigma = jnp.mean(x), jnp.std(x) + 1e-12
    thr = jnp.abs(norm.ppf(1.0 - k / d, mu, sigma))
    lo, hi = 2.0 * k / 3.0, 4.0 * k / 3.0
    ax = jnp.abs(x)
    done = jnp.bool_(False)
    for _ in range(refine_iters):
        est = jnp.sum(ax > thr).astype(jnp.float32)
        new = jnp.where(est < lo, 0.5 * thr, jnp.where(est > hi, 1.5 * thr,
                                                       thr))
        thr = jnp.where(done, thr, new)
        done = done | ((est >= lo) & (est <= hi))
    over = ax > thr
    keep = over & (jnp.cumsum(over) <= k_cap)
    sel = jnp.where(keep, u, jnp.zeros_like(u))
    return sel, u - sel


# ---------------------------------------------------------------------------
# the first steps of training, for the comparison
# ---------------------------------------------------------------------------


def leaf_norms(tree) -> np.ndarray:
    return np.asarray([float(_norm(x)) for x in jax.tree.leaves(tree)])


def leaf_counts(tree) -> np.ndarray:
    return np.asarray([int(_count(x)) for x in jax.tree.leaves(tree)])


def first_steps(family, model: dict, job: dict, seed: int, *,
                n_steps: int = 3, dtype=jnp.float32) -> dict:
    """Run the reference (or, with ``dtype=bfloat16``, the control) of
    ``family``'s ``model`` through the first ``n_steps`` steps of the job
    from ``seed``.

    Returns ``loss`` (one per step), ``update_norms`` and
    ``update_counts`` (per leaf: the norm and the nonzero count of the
    first aggregated gradient, i.e. of the momentum after one step),
    ``first_change_norms`` and ``change_norms`` (per leaf: the
    parameters' change after one step and after ``n_steps``), ``grad_norms`` (per leaf: the raw mean gradient of
    step 0, for the rule that leaves out leaves with no gradient) and
    ``budgets`` (per leaf: Gaussian-k's k).
    """
    W = job["workers"]
    B, S = job["batch_per_worker"] * W, job["seq"]
    lr, mu, ratio = job["lr"], job["momentum"], job["ratio"]
    prec = "highest" if dtype == jnp.float32 else "default"
    cast = partial(jax.tree.map, lambda x: x.astype(dtype))
    make = jax.jit(partial(init_params, family.param_shapes(model)))
    params = cast(make(jax.random.PRNGKey(seed)))
    shapes = [x.shape for x in jax.tree.leaves(params)]
    budgets = [gaussiank_budget(int(np.prod(s)), ratio) for s in shapes]
    treedef = jax.tree.structure(params)

    @jax.jit
    def grad_fn(params, tokens, labels):
        with jax.default_matmul_precision(prec):
            return jax.value_and_grad(family.loss)(params, tokens, labels,
                                                   model)

    @partial(jax.jit, static_argnums=(2, 3))
    def ef_leaf(g, e, k, k_cap):
        u = g.reshape(-1).astype(dtype) + e
        return gaussiank_ef(u, k, k_cap)

    resid = [[jnp.zeros(int(np.prod(s)), dtype) for s in shapes]
             for _ in range(W)]
    mom = [jnp.zeros(s, dtype) for s in shapes]
    out = {"loss": [], "budgets": [k for k, _ in budgets]}
    for step in range(n_steps):
        batch = lm_batch(step, global_batch=B, seq_len=S,
                         vocab=model["vocab_size"], seed=seed)
        agg = [None] * len(shapes)      # sum of the workers' selections
        gsum = [None] * len(shapes)     # sum of their gradients, step 0
        losses = []
        for w in range(W):
            rows = slice(w * B // W, (w + 1) * B // W)
            lval, g = grad_fn(params, batch["tokens"][rows],
                              batch["labels"][rows])
            losses.append(float(lval))
            g = jax.tree.leaves(g)
            for j, (k, kc) in enumerate(budgets):
                gl, g[j] = g[j], None   # free each leaf once it is used
                if step == 0:
                    # the mean gradient's norm; one worker needs no copy
                    gsum[j] = (_norm(gl) if W == 1 else _acc(
                        gsum[j], gl.astype(jnp.float32) / W))
                sel, resid[w][j] = ef_leaf(gl, resid[w][j], k, kc)
                del gl
                agg[j] = _acc(agg[j], sel)
                del sel
        out["loss"].append(float(np.mean(losses)))
        if step == 0:
            out["grad_norms"] = np.asarray(
                [float(x) if W == 1 else float(_norm(x)) for x in gsum])
        del gsum
        new = []
        for j, p in enumerate(jax.tree.leaves(params)):
            mom[j] = (mu * mom[j] + (agg[j].astype(jnp.float32) / W)
                      .reshape(p.shape).astype(dtype)).astype(dtype)
            agg[j] = None
            new.append((p - lr * mom[j]).astype(dtype))
        if step == 0:
            out["update_norms"] = leaf_norms(mom)
            out["update_counts"] = leaf_counts(mom)
            out["first_change_norms"] = _change_norms(
                new, jax.tree.leaves(params))
        params = jax.tree.unflatten(treedef, new)
        del new
    p0 = cast(make(jax.random.PRNGKey(seed)))
    out["change_norms"] = _change_norms(jax.tree.leaves(params),
                                        jax.tree.leaves(p0))
    return out


def _change_norms(new, old) -> np.ndarray:
    return np.asarray(
        [float(_norm(a.astype(jnp.float32) - b.astype(jnp.float32)))
         for a, b in zip(new, old)])


def _acc(total, x):
    return x if total is None else total + x


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _count(x):
    return jnp.count_nonzero(x)

