"""Production mesh definitions.

Kept as FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — required because tests run with 1 device while the
dry-run forces 512 host devices via XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``: the model code places
    arrays with sharding constraints and shard_map, and leaves the rest to
    GSPMD.  ``make_mesh``'s own default (``Explicit``) types every array by
    its sharding and refuses the embedding gather."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds a leading 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2):
    """(data, model) mesh over the first data * model visible devices."""
    return _auto_mesh((data, model), ("data", "model"))


def parse_mesh(spec: str):
    """Build a (data, model) mesh from a CLI spec like ``"2x2"`` or ``"4x1"``.

    ``"none"`` / ``""`` return None (meshless engine).  The product must not
    exceed the visible device count — under CPU CI that count is raised via
    ``--xla_force_host_platform_device_count`` before jax is imported.
    """
    if not spec or spec.lower() == "none":
        return None
    try:
        data, model = (int(p) for p in spec.lower().split("x"))
    except ValueError as e:
        raise ValueError(f"mesh spec must look like 'DxT', got {spec!r}") from e
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got {spec!r}")
    n = data * model
    if n > jax.device_count():
        raise ValueError(
            f"mesh {spec!r} needs {n} devices but only {jax.device_count()} "
            "are visible (set --xla_force_host_platform_device_count)")
    return make_test_mesh(data, model)


def axis_info(mesh) -> dict:
    """dp/tp axis naming convention for a mesh."""
    names = mesh.axis_names
    dp = tuple(a for a in names if a in ("pod", "data"))
    return {"dp_axes": dp, "tp_axis": "model" if "model" in names else None}
