"""Production mesh construction (TPU v5e pods; host-device dry-run on CPU).

Defined as FUNCTIONS so importing this module never touches jax device state
(the dry-run sets XLA_FLAGS before any jax init; smoke tests see 1 device).
"""
from __future__ import annotations

import jax

from repro.sharding.rules import data_extent  # noqa: F401  (single source)


def _make_mesh(shape, axes):
    # The engine's plans place state through NamedShardings on Auto axes
    # (jax.make_mesh defaults to Explicit).
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (CPU host testing)."""
    return _make_mesh((data, model), ("data", "model"))


def parse_host_mesh(spec: str):
    """'DATAxMODEL' CLI spec (e.g. '4x2') -> host mesh."""
    try:
        data, model = (int(x) for x in spec.split("x"))
    except ValueError:
        raise SystemExit(
            f"--mesh expects 'DATAxMODEL' (e.g. 4x2), got {spec!r}") from None
    return make_host_mesh(data, model)


def model_extent(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("model", 1)
