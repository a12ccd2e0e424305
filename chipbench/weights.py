"""Seeded weights in the type they are served in, made on the device.

The benchmark, not the program, makes the weights: a reference family
(``reference/<family>.py``) lists their names, shapes and kinds, and
one jitted call draws every array from the seed as bfloat16 on the
default device.  The reference reads these arrays; the program gets the
same arrays arranged as its parameter tree.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def make(specs, seed: int, dtype=jnp.bfloat16):
    """Arrays for ``specs`` (name -> (shape, kind)) from ``seed``, which
    may be any whole number: numpy folds it into the 31-bit key JAX
    takes.  Kinds: 'embed' N(0, 0.02), 'dense' N(0, 1/fan-in) with the
    fan-in the second-to-last dimension, 'norm' 1 + N(0, 0.1)."""
    names = sorted(specs)
    key = int(np.random.default_rng(seed).integers(2**31))

    def init(k):
        out = {}
        for i, name in enumerate(names):
            shape, kind = specs[name]
            z = jax.random.normal(jax.random.fold_in(k, i), shape, jnp.float32)
            if kind == "embed":
                v = 0.02 * z
            elif kind == "dense":
                v = z / math.sqrt(shape[-2])
            elif kind == "norm":
                v = 1.0 + 0.1 * z
            else:
                raise ValueError(f"unknown weight kind {kind!r}")
            out[name] = v.astype(dtype)
        return out

    return jax.jit(init)(jax.random.key(key))
