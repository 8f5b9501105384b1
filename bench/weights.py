"""Random weights from the seed, drawn the same way for the program and for
the reference.

Every tensor is ``normal(key(seed, tensor, layer), shape) * scale`` in
float32, rounded to the served dtype by the kind that draws it
(``bench/kinds``). Each kind declares the ids of the tensors it names; a
name has one id in every kind, so that a name always draws the same
numbers, and two names never share an id.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# tensor name -> id, as the kinds declare them
_IDS: dict = {}


def declare(ids: dict) -> dict:
    """Adds a kind's tensor ids (name -> id) and returns them. A name
    declared with another id than before, or an id declared for two names,
    is an error."""
    taken = {i: n for n, i in _IDS.items()}
    for name, i in ids.items():
        if _IDS.get(name, i) != i or taken.get(i, name) != name:
            raise ValueError(f"tensor {name!r} as id {i} collides with "
                             f"{taken.get(i, name)!r} as id "
                             f"{_IDS.get(name, i)}")
        taken[i] = name
    _IDS.update(ids)
    return ids


def base_key(seed: int):
    """A key from any whole number up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


NORM = None        # a norm's scale: 1 + 0.1 * normal


def draw(key, name: str, shape, fan_in, layer=None):
    """float32 normal draws for one tensor, scaled by 1/sqrt(fan-in); a
    norm's scale is 1 + 0.1 * normal."""
    k = jax.random.fold_in(key, _IDS[name])
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    x = jax.random.normal(k, shape, jnp.float32)
    if fan_in is NORM:
        return 1.0 + 0.1 * x
    return x / math.sqrt(fan_in)
