"""Which rows each stochastic-depth branch keeps in a training step.

Stochastic depth is part of the input of a step, like the rows of the batch:
the reference has to drop what the program dropped. The program draws each
branch's rows with ``jax.random.bernoulli`` from a key that follows from the
run's seed alone, by the rule of ``flax.linen.Module.make_rng``: the step's
key with the SHA-1 of the module's path and call count folded in. That rule is
written out here (hashlib and jax.random only), so the masks are recomputed
from the seed, not read from the program.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp


def step_key(seed: int, step: int, process_index: int = 0):
    """The key a step's random draws start from: the seed's key, the
    process's index and the step's number folded in."""
    key = jax.random.fold_in(jax.random.key(seed), process_index)
    return jax.random.fold_in(key, jnp.asarray(step, jnp.uint32))


def module_key(key, path: tuple, count: int = 1):
    digest = hashlib.sha1()
    for part in (*path, count):
        if isinstance(part, str):
            digest.update(part.encode("utf-8"))
        else:
            digest.update(part.to_bytes((part.bit_length() + 7) // 8, "big"))
    word = int.from_bytes(digest.digest()[:4], "big")
    return jax.random.fold_in(key, jnp.uint32(word))


def keep_factors(seed: int, step: int, sites: list, rows: int):
    """(sites, rows) float32: 0 where a branch drops a row, 1/(1 - rate)
    where it keeps it. None where the configuration has no such branch."""
    if not sites:
        return None
    key = step_key(seed, step)
    out = []
    for path, rate in sites:
        kept = jax.random.bernoulli(module_key(key, path), 1.0 - rate, (rows, 1, 1))
        out.append(kept.reshape(rows).astype(jnp.float32) / (1.0 - rate))
    return jnp.stack(out)
