"""Production mesh builders.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — required for the smoke tests / benches to
see 1 CPU device while dryrun.py forces 512 host devices.
"""

from __future__ import annotations

import jax


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis Auto: the sharding rules here are
    GSPMD constraints, not the explicit-axis typing that ``make_mesh``
    defaults to."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None):
    """Small mesh over whatever devices exist (CPU tests)."""
    n = n_devices or len(jax.devices())
    if n % 2 == 0:
        return _auto_mesh((n // 2, 2), ("data", "model"))
    return _auto_mesh((n, 1), ("data", "model"))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes that carry the batch dimension (pod composes with data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1
