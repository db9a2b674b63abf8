"""Random weights from the seed, made by the benchmark, not the program.

The tree is the layout the program's transformer takes (``embed``, ``head``,
``final_ln`` and the layer stack with its leading layer axis), so the same
weights feed the system under test and the plain reference, and the
reference takes nothing the program made. They are made on the device in
one jitted call, in float32, the type the program keeps its parameters in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def shapes(cfg: dict) -> dict:
    """Leaf shapes of the parameter tree for configuration ``cfg``."""
    d, h, hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    hd, f, nl, v = (cfg["head_dim"], cfg["intermediate_size"],
                    cfg["num_hidden_layers"], cfg["vocab_size"])
    return {
        "embed": (v, d), "head": (d, v), "final_ln": (d,),
        "layers": {
            "ln1": (nl, d), "ln2": (nl, d),
            "attn": {"wq": (nl, d, h, hd), "wk": (nl, d, hkv, hd),
                     "wv": (nl, d, hkv, hd), "wo": (nl, h, hd, d)},
            "mlp": {"w_gate": (nl, d, f), "w_up": (nl, d, f),
                    "w_down": (nl, f, d)},
        },
    }


def _fan_in(path: str, shape) -> int:
    """Inputs that each output of a matrix sums over."""
    if path.endswith("wo"):
        return shape[-3] * shape[-2]          # heads x head_dim
    return shape[1] if len(shape) > 2 else shape[0]


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def make(cfg: dict, key: jax.Array) -> dict:
    """Weights for ``cfg`` from ``key``: embedding N(0, 0.02), matrices
    truncated normal over sqrt(fan_in), norm scales 1. One jitted call."""
    tree = shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=_is_shape)
    names = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in flat]
    leaf_shapes = tuple(tuple(s) for _, s in flat)
    leaves = _make(tuple(names), leaf_shapes, key)
    return jax.tree_util.tree_unflatten(treedef, leaves)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make(names, leaf_shapes, key):
    keys = jax.random.split(key, len(names))
    out = []
    for name, shape, k in zip(names, leaf_shapes, keys):
        if name.endswith(("ln", "ln1", "ln2")):
            out.append(jnp.ones(shape, jnp.float32))
        elif name == "embed":
            out.append(0.02 * jax.random.normal(k, shape, jnp.float32))
        else:
            std = 1.0 / (_fan_in(name, shape) ** 0.5)
            out.append(std * jax.random.truncated_normal(
                k, -2.0, 2.0, shape, jnp.float32))
    return out
