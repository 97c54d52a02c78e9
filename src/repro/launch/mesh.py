"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — required because the
dry-run pins the device count via XLA_FLAGS before any jax init, while tests
and benchmarks must keep seeing the single real CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "make_grid_mesh",
           "single_device_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (elastic re-mesh path; see runtime.plan_mesh).

    Uses the first prod(shape) devices so a 256-chip mesh builds fine in the
    512-placeholder-device dry-run process.
    """
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(f"need {n} devices, have {len(devs)} "
                           "(dry-run must set xla_force_host_platform_device_count)")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_grid_mesh(units_x: int, units_y: int):
    """Mesh for a ``units_x`` × ``units_y`` PE-array grid backend.

    Axes are ``("gx", "gy")`` — ``gx`` is the contraction-dim partition the
    partial-sum psum reduces over, ``gy`` the output-column partition (see
    ``repro.backends.grid``).  Deliberately disjoint from the model-parallel
    axis names (``data``/``model``/``pod``) so the modeling layer's logical
    sharding rules all fall back to replication on a grid mesh and the only
    partitioned compute is the grid's own shard_map.

    Needs ``units_x * units_y`` visible devices (pin fake host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax init).
    """
    return make_mesh((units_x, units_y), ("gx", "gy"))


def single_device_mesh(model_axis: bool = True):
    """Trivial mesh for CPU tests: same axis names, size-1 axes."""
    if model_axis:
        return make_mesh((1, 1), ("data", "model"))
    return make_mesh((1,), ("data",))
