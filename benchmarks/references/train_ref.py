"""Plain reference of the training step the configurations state: label-
smoothed cross entropy over the global batch, gradient, clip by global norm,
AdamW (decoupled decay on matrices only, bias-corrected), linear warm-up of
the learning rate, and the exponential moving average of the parameters.
float32 throughout; matrix products in the ``mode`` of ``ops``. The batch is
worked in blocks of rows so that float32 activations fit beside the state.
Imports nothing of the program.
"""

from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp

NO_DECAY = ("bias", "scale", "norm", "pos_embed", "cls_token",
            "relative_position_bias")


def family(name: str):
    return importlib.import_module(f"benchmarks.references.{name}")


def leaf_paths(tree) -> list:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


def make_params(spec: dict, seed: int):
    """Weights from the seed in one jitted call: N(0, 0.02) for ``normal``
    leaves, ones and zeros for norm scales and biases. float32, the type the
    configurations keep parameters in."""
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))

    def build(key):
        out = []
        for i, (shape, kind) in enumerate(leaves):
            if kind == "normal":
                out.append(0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32))
            elif kind == "ones":
                out.append(jnp.ones(shape, jnp.float32))
            else:
                out.append(jnp.zeros(shape, jnp.float32))
        return out
    return jax.tree.unflatten(treedef, jax.jit(build)(jax.random.key(seed)))


def learning_rate(recipe: dict, count: int) -> float:
    """Linear warm-up from ``warmup_lr`` to ``lr`` over ``warmup_steps``; the
    steps a run follows all lie inside the warm-up."""
    if count >= recipe["warmup_steps"]:
        raise ValueError("the reference follows warm-up steps only")
    frac = count / recipe["warmup_steps"]
    return recipe["warmup_lr"] + (recipe["lr"] - recipe["warmup_lr"]) * frac


def ema_decay(recipe: dict, step: int) -> float:
    return recipe["ema_decay"] * (1.0 - math.exp(-step / recipe["ema_ramp_steps"]))


def _loss_sum(params, images, labels, keep, shapes, fam, mode, smoothing):
    logits = fam.forward(params, images, shapes, mode, keep)
    classes = logits.shape[-1]
    target = jax.nn.one_hot(labels, classes) * (1.0 - smoothing) \
        + smoothing / classes
    return -jnp.sum(target * jax.nn.log_softmax(logits, axis=-1))


@functools.lru_cache(maxsize=None)
def _block_grad(fam_name: str, shapes_items: tuple, mode: str, smoothing: float):
    shapes = dict(shapes_items)
    fam = family(fam_name)
    fn = functools.partial(_loss_sum, shapes=shapes, fam=fam, mode=mode,
                           smoothing=smoothing)
    return jax.jit(jax.value_and_grad(fn))


def loss_and_grad(params, images, labels, keep, *, fam_name, shapes, mode,
                  smoothing, rows):
    """Mean loss over the whole batch and its gradient, block by block."""
    step = _block_grad(fam_name, tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in shapes.items())),
        mode, float(smoothing))
    n = images.shape[0]
    total, grads = 0.0, None
    for lo in range(0, n, rows):
        sl = slice(lo, lo + rows)
        part, g = step(params, images[sl], labels[sl],
                       None if keep is None else keep[:, sl])
        total = total + part
        grads = g if grads is None else _tree_add(grads, g)
    return total / n, grads


_tree_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)


@jax.jit
def _mean_and_clip(grads, n, clip):
    """The summed gradient over n rows -> (the mean gradient after the clip by
    global norm, the norm before it)."""
    grads = jax.tree.map(lambda g: g / n, grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(grads)))
    scale = jnp.where(gnorm < clip, 1.0, clip / gnorm)
    return jax.tree.map(lambda g: g * scale, grads), gnorm


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def tree_diff(a, b):
    return [x.astype(jnp.float32) - y
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


@jax.jit
def masked_leaf_norms(leaves, grads, floor):
    """Per-leaf norm over the elements whose gradient in ``grads`` is at
    least ``floor`` in size."""
    return [jnp.sqrt(jnp.sum(jnp.square(jnp.where(jnp.abs(g) >= floor, x, 0.0))))
            for x, g in zip(leaves, grads)]


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "decay"))
def _adamw(params, mu, nu, ema, grads, lr, count, ema_d, decay_mask, *,
           b1, b2, eps, decay):
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count

    def new(p, m, v, d):
        u = (m / c1) / (jnp.sqrt(v / c2) + eps)
        return p - lr * (u + decay * d * p)
    params = jax.tree.map(new, params, mu, nu, decay_mask)
    ema = jax.tree.map(lambda e, p: e * ema_d + p * (1 - ema_d), ema, params)
    return params, mu, nu, ema


def follow(*, fam_name: str, shapes: dict, recipe: dict, params, batches,
           keeps, mode: str = "f32", rows: int = 32, skip_rows=None) -> dict:
    """Drive the stated training step over ``batches`` (a list of (images
    float32, labels) of the global batch) from ``params``. Returns each step's
    loss and global gradient norm, the per-leaf norm of the first gradient as
    the optimizer's moments receive it (after the clip), and the per-leaf norms
    of the parameters' and of the average's change over all the steps (kept
    as arrays, with the first gradient, so that the comparison can leave out
    single elements).

    ``skip_rows`` plants the fault "part of the batch left out, the mean taken
    over the rest" (a slice of row positions to drop), for the controls."""
    paths = leaf_paths(params)
    decay_mask = jax.tree.unflatten(
        jax.tree.structure(params),
        [float(x.ndim >= 2 and not any(k in p.lower() for k in NO_DECAY))
         for p, x in zip(paths, jax.tree.leaves(params))])
    start = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    ema = params
    out = {"paths": paths, "loss": [], "grad_norm": []}
    for t, ((images, labels), keep) in enumerate(zip(batches, keeps)):
        images, labels = jnp.asarray(images), jnp.asarray(labels)
        if skip_rows is not None:
            pick = jnp.asarray([i for i in range(images.shape[0])
                                if i not in skip_rows])
            images, labels = images[pick], labels[pick]
            keep = None if keep is None else keep[:, pick]
        loss, grads = loss_and_grad(
            params, images, labels, keep, fam_name=fam_name, shapes=shapes,
            mode=mode, smoothing=recipe["label_smoothing"], rows=rows)
        grads, gnorm = _mean_and_clip(grads, float(images.shape[0]),
                                      recipe["clip_grad_norm"])
        if t == 0:
            out["first_grad"] = [float(x) for x in leaf_norms(grads)]
            out["first_grad_leaves"] = jax.tree.leaves(grads)
        params, mu, nu, ema = _adamw(
            params, mu, nu, ema, grads, learning_rate(recipe, t), t + 1,
            ema_decay(recipe, t + 1), decay_mask, b1=recipe["b1"],
            b2=recipe["b2"], eps=recipe["eps"], decay=recipe["weight_decay"])
        out["loss"].append(loss)
        out["grad_norm"].append(gnorm)
    out["loss"] = [float(x) for x in out["loss"]]
    out["grad_norm"] = [float(x) for x in out["grad_norm"]]
    out["change"] = tree_diff(params, start)
    out["ema_change"] = tree_diff(ema, start)
    return out
