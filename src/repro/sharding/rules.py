"""Logical-axis sharding rules -> PartitionSpec over the production mesh.

Params are built with logical axis names attached per dimension (see
``models/layers.py: Param``); the rules below map names to mesh axes. jit
*arguments* must divide evenly on every sharded dim (JAX requirement), so
config code pads vocab / expert counts and falls back per the attention-mode
table in DESIGN.md §5; *intermediates* may use uneven constraints (GSPMD pads).
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as PS

# Architectures whose params/optimizer also shard over the data axis (ZeRO /
# FSDP-style "embed" -> data) — required to fit the big configs on v5e HBM.
FSDP_ARCHS = {"kimi-k2-1t-a32b", "deepseek-67b"}

# logical axis -> mesh axis (None = replicated). "batch" spans pod+data.
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "cache_batch": ("pod", "data"),
    "seq": None,
    "cache_seq": "model",       # seq-sharded KV cache (flash-decoding layout)
    "vocab": "model",
    "embed": None,              # switched to ("pod","data") by fsdp=True
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "head_dim_sharded": "model",  # contraction-mode wo
    "mlp": "model",
    "d_sharded": "model",       # contraction-mode qkv input dim
    "experts": "model",
    "expert_mlp": None,
    "layers": None,
    "ssm_heads": "model",
    "ssm_inner": "model",
    "state": None,
    "conv": None,
    "replicated": None,
}


def rules_for(fsdp: bool = False, extra: Optional[dict] = None) -> dict:
    rules = dict(DEFAULT_RULES)
    if fsdp:
        rules["embed"] = ("pod", "data")
    if extra:
        rules.update(extra)
    return rules


def data_extent(mesh: Mesh) -> int:
    """Total data-parallel worker count (pods x data)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("data", 1) * sizes.get("pod", 1)


def worker_axes(mesh: Mesh):
    """Mesh axes a leading worker dimension shards over: ("pod","data") kept
    as available, collapsed to a single name or None like spec_for does."""
    kept = tuple(a for a in ("pod", "data") if a in set(mesh.axis_names))
    return kept if len(kept) > 1 else (kept[0] if kept else None)


def worker_axis_split(mesh: Optional[Mesh], p: int) -> bool:
    """Does a leading worker axis of extent ``p`` lie split over several
    devices? The planner shards it over ``worker_axes(mesh)`` only where
    the data extent divides ``p`` evenly (jit arguments must), and a data
    extent of 1 splits nothing. No mesh: one device."""
    if mesh is None or worker_axes(mesh) is None:
        return False
    extent = data_extent(mesh)
    return extent > 1 and p % extent == 0


def rules_for_arch(arch_id: Optional[str], shape=None, mesh: Optional[Mesh] = None,
                   extra: Optional[dict] = None) -> dict:
    """The rule set the sharding planner uses for one (arch, shape, mesh):
    FSDP placement for the ZeRO-class archs, plus the even-division fallback —
    jit args must divide evenly, so a global batch smaller than the data
    extent (long_500k: batch=1) is replicated instead."""
    rules = rules_for(fsdp=arch_id in FSDP_ARCHS, extra=extra)
    if shape is not None and mesh is not None:
        if shape.global_batch % data_extent(mesh):
            rules["batch"] = None
            rules["cache_batch"] = None
    return rules


def strip_data(rules: dict) -> dict:
    """Rules with pod/data targets removed (model-axis sharding only) — for
    state whose leading worker dimension already occupies the data axis (a
    PartitionSpec may not use a mesh axis twice)."""
    def clean(v):
        if isinstance(v, tuple):
            kept = tuple(a for a in v if a not in ("pod", "data"))
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return None if v in ("pod", "data") else v
    return {k: clean(v) for k, v in rules.items()}


def _mesh_axes(mesh: Mesh) -> set:
    return set(mesh.axis_names)


def spec_for(axes: Sequence[Optional[str]], mesh: Mesh, rules: dict) -> PS:
    """Logical axes tuple -> PartitionSpec, dropping mesh axes that do not
    exist on this mesh (e.g. 'pod' on the single-pod mesh)."""
    have = _mesh_axes(mesh)
    parts = []
    for name in axes:
        if name is None:
            parts.append(None)
            continue
        target = rules.get(name, None)
        if target is None:
            parts.append(None)
        elif isinstance(target, tuple):
            kept = tuple(t for t in target if t in have)
            parts.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            parts.append(target if target in have else None)
    return PS(*parts)


def tree_specs(axes_tree: Any, mesh: Mesh, rules: Optional[dict] = None) -> Any:
    """Map a tree of logical-axes tuples to a tree of PartitionSpec."""
    rules = rules or DEFAULT_RULES
    return jax.tree.map(
        lambda axes: spec_for(axes, mesh, rules),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def tree_shardings(axes_tree: Any, mesh: Mesh, rules: Optional[dict] = None) -> Any:
    specs = tree_specs(axes_tree, mesh, rules)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, PS),
    )


def pad_to_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def batch_spec(mesh: Mesh) -> PS:
    have = _mesh_axes(mesh)
    axes = tuple(a for a in ("pod", "data") if a in have)
    return PS(axes if len(axes) > 1 else axes[0])


def constraint(x, mesh: Mesh, *axes: Optional[str], rules: Optional[dict] = None):
    """with_sharding_constraint by logical axes (uneven dims allowed here)."""
    rules = rules or DEFAULT_RULES
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_for(axes, mesh, rules))
    )


def ambient_mesh() -> Optional[Mesh]:
    """The mesh installed by ``with mesh:`` (None outside any context)."""
    from jax._src import mesh as mesh_lib

    m = mesh_lib.thread_resources.env.physical_mesh
    return None if m.empty else m


def ambient_constraint(x, *parts: Optional[str]):
    """with_sharding_constraint against the ambient mesh; no-op when there is
    none (CPU smoke tests) or when the named axes don't exist. Uneven dims are
    fine — intermediates are padded by GSPMD. Model code uses this to steer
    activation sharding without threading a mesh handle through every layer."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    have = set(mesh.axis_names)

    def clean(p):
        if p == "UNC":
            return PS.UNCONSTRAINED
        if isinstance(p, tuple):
            kept = tuple(a for a in p if a in have)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return p if p in have else None

    cleaned = tuple(clean(p) for p in parts)
    if all(c is None or c is PS.UNCONSTRAINED for c in cleaned):
        return x
    return jax.lax.with_sharding_constraint(x, PS(*cleaned))
