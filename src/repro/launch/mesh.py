"""Production meshes (TPU v5e).

Single pod: 256 chips as (16, 16) = (data, model).
Multi-pod: 2 pods × 256 chips as (2, 16, 16) = (pod, data, model);
the ``pod`` axis is the *federated* axis — one FedZO client per pod
(DESIGN.md §3.3).

Defined as functions so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS before calling.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_clients_mesh(n_devices: int = 0, *, axis: str = "clients"):
    """1-D mesh over the local devices with the federated ``clients`` axis —
    the simulation engine's fan-out mesh (repro.sim.shard): the M sampled
    clients of each round split over this axis, one shard of local phases
    and one partial aggregation reduce per device."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if n_devices:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def make_host_mesh(model_axis: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return _make_mesh((n // model_axis, model_axis), ("data", "model"))


def data_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
