"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod = 16×16 = 256 chips (v5e pod);
multi-pod = 2×16×16 = 512 chips with a leading "pod" axis (DCI-connected).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh(model: int = 1):
    """Smoke-test mesh over whatever devices exist (usually 1 CPU device).

    ``model`` must divide the device count: ``data`` is the cofactor, and a
    non-divisor would build a ``data * model != n`` mesh that ``make_mesh``
    rejects with an opaque reshape error (or, worse, silently drop devices).
    """
    n = len(jax.devices())
    if model < 1:
        raise ValueError(f"model axis must be >= 1, got {model}")
    if model > n or n % model != 0:
        raise ValueError(
            f"model={model} does not divide the local device count {n} "
            f"(valid: {[d for d in range(1, n + 1) if n % d == 0]}); "
            f"force more host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return _mk((n // model, model), ("data", "model"))


def make_serve_meshes(tp: int = 1, dp: int = 1):
    """``dp`` single-axis ``('model',)`` meshes of ``tp`` devices each, over
    disjoint contiguous device groups — one mesh per data-parallel engine
    replica.  Serving replicas never communicate across ``data`` (each owns
    its pool, page tables, and scheduler inventory), so they get independent
    meshes rather than one global ``(data, model)`` mesh: a replica's jitted
    steps shard_map over its own ``model`` axis only."""
    if tp < 1 or dp < 1:
        raise ValueError(f"tp and dp must be >= 1, got tp={tp} dp={dp}")
    devices = jax.devices()
    need = tp * dp
    if need > len(devices):
        raise ValueError(
            f"tp={tp} x dp={dp} needs {need} devices, have {len(devices)}; "
            f"force more host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
    import numpy as np

    return [Mesh(np.asarray(devices[r * tp:(r + 1) * tp]), ("model",))
            for r in range(dp)]
