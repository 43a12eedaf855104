"""Production meshes (spec: MULTI-POD DRY-RUN step 1).

Importing this module never touches jax device state; meshes are built
inside the function.  Single pod: (16, 16) = 256 chips, axes
("data", "model").  Multi-pod: (2, 16, 16) = 512 chips with a leading
"pod" axis that composes with "data" for batch/grid/FSDP sharding.

Topology (ISSUE 4): the drain engine's host streams are built from these
meshes — ``split_pod_meshes`` carves a multi-pod production mesh into one
("data", "model") mesh per pod, and ``make_sim_host_meshes`` fakes N
hosts out of whatever devices this process has (the forced-host-platform
CI path: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host offers (tests / examples)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def split_pod_meshes(mesh):
    """One ("data", "model")-style mesh per index of the leading "pod"
    axis — the per-host meshes the topology layer streams over."""
    if "pod" not in mesh.axis_names:
        return [mesh]
    from jax.sharding import Mesh
    pod_axis = mesh.axis_names.index("pod")
    axes = tuple(a for a in mesh.axis_names if a != "pod")
    devs = np.asarray(mesh.devices)
    return [Mesh(np.take(devs, i, axis=pod_axis), axes)
            for i in range(devs.shape[pod_axis])]


def make_sim_host_meshes(n_hosts: int, model_parallel: int = 1):
    """N simulated host meshes over this process's devices.

    Devices are split contiguously; with fewer devices than hosts the
    tail hosts reuse devices round-robin (pure simulation — residency
    separation still holds because each host owns its own page pool).
    A host group too small for the requested ``model_parallel`` falls
    back to data-parallel-only rather than failing.
    """
    from jax.sharding import Mesh
    devs = jax.devices()
    per = max(len(devs) // max(n_hosts, 1), 1)
    meshes = []
    for h in range(n_hosts):
        group = devs[h * per:(h + 1) * per] or [devs[h % len(devs)]]
        mp = model_parallel if len(group) % model_parallel == 0 else 1
        arr = np.asarray(group).reshape(len(group) // mp, mp)
        meshes.append(Mesh(arr, ("data", "model")))
    return meshes
