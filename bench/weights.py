"""Random weights from the seed, made by the benchmark, not the program.

The tree is the layout the program takes for the configuration's family
(``shapes`` of ``bench/models/<model>.py``), so the same weights feed the
system under test and the plain reference, and the reference takes nothing
the program made. They are made on the device in one jitted call, in
float32, the type the program keeps its parameters in.

Each leaf is made by its name: a leaf whose name ends in ``ln``, ``ln1``
or ``ln2`` is a norm scale, all ones; ``embed`` is N(0, 0.02); every other
leaf is truncated normal over the square root of the family's ``fan_in``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def make(model, cfg: dict, key: jax.Array) -> dict:
    """Weights for ``cfg`` of the family ``model`` from ``key``. One jitted
    call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        model.shapes(cfg), is_leaf=_is_shape)
    names = tuple("/".join(str(getattr(k, "key", k)) for k in path)
                  for path, _ in flat)
    leaf_shapes = tuple(tuple(s) for _, s in flat)
    fans = tuple(model.fan_in(n, s) for n, s in zip(names, leaf_shapes))
    leaves = _make(names, leaf_shapes, fans, key)
    return jax.tree_util.tree_unflatten(treedef, leaves)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _make(names, leaf_shapes, fans, key):
    keys = jax.random.split(key, len(names))
    out = []
    for name, shape, fan, k in zip(names, leaf_shapes, fans, keys):
        if name.endswith(("ln", "ln1", "ln2")):
            out.append(jnp.ones(shape, jnp.float32))
        elif name == "embed":
            out.append(0.02 * jax.random.normal(k, shape, jnp.float32))
        else:
            std = 1.0 / (fan ** 0.5)
            out.append(std * jax.random.truncated_normal(
                k, -2.0, 2.0, shape, jnp.float32))
    return out
