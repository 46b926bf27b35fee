"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
jax initialization.

Topology model (TPU v5e-class):
  single pod:  (16, 16)    axes (data, model)   = 256 chips
  multi pod:   (2, 16, 16) axes (pod, data, model) = 512 chips
"model" is the innermost axis (fastest ICI neighborhood); "pod" is the
slow DCN-class axis that the 1-bit gradient compression targets.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_engine_mesh(n_devices: int | None = None):
    """1-D mesh over the host's devices, axis ``bank``.

    The sharded CiM engine's mesh-as-outer-bank-dimension model
    (DESIGN.md §11): every device carries one local bank stack, so the
    engine's throughput tier is ``devices x banks x cols`` bits/cycle.
    Takes the first ``n_devices`` devices (all by default) — unlike the
    production meshes this axis has no topology constraint, engine traffic
    is embarrassingly parallel except for the 512-byte digest reduce.
    """
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, host has {len(devs)}")
    return Mesh(np.asarray(devs[:n]), ("bank",))


def make_replica_meshes(n_replicas: int):
    """Partition the host's devices into one 1-D ``bank`` sub-mesh per
    serve replica (DESIGN.md §17).

    With at least one device per replica each sub-mesh gets a disjoint
    contiguous slice of ``len(devices) // n_replicas`` devices (the
    remainder stays unused — equal-width replicas keep the straggler
    policy's per-step timing comparable); with fewer devices than replicas
    the sub-meshes wrap round-robin and replicas share.  The router pins
    each replica's programs to its sub-mesh's first device, so under the
    8-virtual-device CI mode replicas genuinely run side by side.
    """
    if n_replicas < 1:
        raise ValueError(f"need at least one replica, got {n_replicas}")
    devs = jax.devices()
    if len(devs) >= n_replicas:
        per = len(devs) // n_replicas
        slices = [devs[i * per:(i + 1) * per] for i in range(n_replicas)]
    else:
        slices = [[devs[i % len(devs)]] for i in range(n_replicas)]
    return [Mesh(np.asarray(s), ("bank",)) for s in slices]


def make_smoke_mesh(n_devices: int | None = None):
    """Tiny mesh for CPU-scale distributed tests (e.g. 8 = 2x2x2)."""
    n = n_devices or len(jax.devices())
    if n >= 8:
        shape, axes = (2, 2, n // 4), ("pod", "data", "model")
    elif n >= 4:
        shape, axes = (2, n // 2), ("data", "model")
    else:
        shape, axes = (1, n), ("data", "model")
    return _make_mesh(shape, axes)
