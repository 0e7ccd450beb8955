"""Plain reference of the language-model training step, beside
``train_ref.py`` (whose clip, AdamW, warm-up and leaf measures it uses): loss =
CE(main head, token i + 1) + ``mtp_loss_weight`` x CE(MTP head, token i + 2),
each a mean over the positions of the global batch that have a target;
gradient, clip by global norm, AdamW, linear warm-up. No label smoothing, no
moving average. float32 throughout; the batch is worked a block of ``rows``
sequences at a time and the summed gradient is added in place, so that 2.8 GB
of parameters, as much of gradient and 5.6 GB of Adam's moments fit on the chip
the program has just left. Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import train_ref


def _weighted_loss(params, rows, shapes, fam, mode, bias_in_choice, counts):
    """This block's part of the batch's loss: each head's sum over the
    head's positions in the whole batch (``counts``), the MTP head weighted.
    (total, per-head sums)"""
    sums = [x for x, _ in fam.loss_sums(params, rows, shapes, mode,
                                        bias_in_choice)]
    weights = [1.0] + [shapes["mtp_loss_weight"]] * (len(sums) - 1)
    return sum(w * x / c for w, x, c in zip(weights, sums, counts)), sums


@functools.lru_cache(maxsize=None)
def _accumulate(fam_name: str, shapes_items: tuple, mode: str,
                bias_in_choice: bool, counts: tuple):
    shapes, fam = dict(shapes_items), train_ref.family(fam_name)
    fn = functools.partial(_weighted_loss, shapes=shapes, fam=fam, mode=mode,
                           bias_in_choice=bias_in_choice, counts=counts)

    def step(params, acc, loss, rows):
        (part, _), g = jax.value_and_grad(fn, has_aux=True)(params, rows)
        return jax.tree.map(jnp.add, acc, g), loss + part
    return jax.jit(step, donate_argnums=(1, 2))


def loss_and_grad(params, tokens, *, fam_name, shapes, mode, rows,
                  bias_in_choice=True):
    """Loss over the whole batch ``tokens`` (b, n + 1) and its gradient,
    ``rows`` sequences at a time."""
    b, n = tokens.shape[0], tokens.shape[1] - 1
    heads = 1 + bool(shapes["num_nextn_predict_layers"])
    counts = tuple(float(b * (n - a)) for a in range(heads))
    step = _accumulate(fam_name, tuple(sorted(shapes.items())), mode,
                       bias_in_choice, counts)
    acc = jax.tree.map(jnp.zeros_like, params)
    loss = jnp.zeros((), jnp.float32)
    for lo in range(0, b, rows):
        acc, loss = step(params, acc, loss, tokens[lo:lo + rows])
    return loss, acc


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "decay"),
                   donate_argnums=(0, 1, 2))
def _adamw(params, mu, nu, grads, lr, count, decay_mask, *, b1, b2, eps, decay):
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count

    def new(p, m, v, d):
        return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + decay * d * p)
    return jax.tree.map(new, params, mu, nu, decay_mask), mu, nu


@functools.partial(jax.jit, donate_argnums=0)
def _clip(grads, clip):
    """(the gradient after the clip by global norm, the norm before it)"""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(grads)))
    scale = jnp.where(gnorm < clip, 1.0, clip / gnorm)
    return jax.tree.map(lambda g: g * scale, grads), gnorm


def follow(*, fam_name: str, shapes: dict, recipe: dict, params, batches,
           mode: str = "f32", rows: int = 1, skip_rows=None,
           bias_in_choice: bool = True) -> dict:
    """Drive the stated step over ``batches`` (token rows (b, n + 1) of the
    global batch) from ``params`` (which it consumes). Returns each step's
    loss and gradient norm, the per-leaf norm of the first gradient as Adam's
    moments receive it (after the clip), and the parameters' change over all
    the steps; the first gradient and the change leaf by leaf on the host, so
    that the comparison can leave out single elements.

    Faults for the controls: ``skip_rows`` leaves those sequences out and
    takes the mean over the rest; ``bias_in_choice`` false leaves the
    correction bias out of the routers' choice."""
    paths = train_ref.leaf_paths(params)
    decay_mask = jax.tree.unflatten(
        jax.tree.structure(params),
        [float(x.ndim >= 2 and not any(k in p.lower()
                                       for k in train_ref.NO_DECAY))
         for p, x in zip(paths, jax.tree.leaves(params))])
    start = [np.asarray(x) for x in jax.tree.leaves(params)]
    mu = nu = None        # made when the first gradient is there, not before
    out = {"paths": paths, "loss": [], "grad_norm": []}
    for t, tokens in enumerate(batches):
        tokens = jnp.asarray(tokens)
        if skip_rows is not None:
            tokens = tokens[jnp.asarray([i for i in range(tokens.shape[0])
                                         if i not in skip_rows])]
        loss, grads = loss_and_grad(
            params, tokens, fam_name=fam_name, shapes=shapes, mode=mode,
            rows=rows, bias_in_choice=bias_in_choice)
        grads, gnorm = _clip(grads, recipe["clip_grad_norm"])
        if t == 0:
            out["first_grad"] = [float(x) for x in train_ref.leaf_norms(grads)]
            out["first_grad_leaves"] = [np.asarray(x)
                                        for x in jax.tree.leaves(grads)]
        if mu is None:
            mu = jax.tree.map(jnp.zeros_like, grads)
            nu = jax.tree.map(jnp.zeros_like, grads)
        params, mu, nu = _adamw(
            params, mu, nu, grads, train_ref.learning_rate(recipe, t), t + 1,
            decay_mask, b1=recipe["b1"], b2=recipe["b2"], eps=recipe["eps"],
            decay=recipe["weight_decay"])
        # 2.8 GB the next step's accumulator needs: with it still held, the
        # second step found 2.59 GB where its program reserves 2.77 (my chip
        # run, PR 32)
        del grads
        out["loss"].append(float(loss))
        out["grad_norm"].append(float(gnorm))
    del mu, nu
    out["change"] = [np.asarray(x) - s0
                     for x, s0 in zip(jax.tree.leaves(params), start)]
    return out
