"""Production meshes.

Target hardware: TPU v5e pods — 256 chips/pod (16x16), 2 pods for the
multi-pod dry-run.  Defined as functions so importing this module never
touches jax device state (device count is locked on first use).
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh

from repro.sharding.rules import MeshAxes

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run "
            "under launch/dryrun.py which forces 512 host devices")
    return jax.make_mesh(shape, axes, devices=devices[:n])


def request_host_devices(n: int) -> None:
    """Ask the CPU backend for ``n`` fake devices, for multi-device paths
    on the host.  Only the CPU platform reads the setting, and only when
    it starts: once a backend is up, its device count stands."""
    try:
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:                # a backend has already started
        pass


def make_host_mesh(n_data: int = 1, *, n_model: int = 1,
                   n_pod: int = 0) -> Mesh:
    """Mesh over the first devices of the default backend, for the chips
    of one host (``train.py --dist``) or CPU tests and examples.

    The default (1, 1) runs on a single device.  On the CPU, multi-device
    variants need fake host devices, asked for before jax starts its
    backend (:func:`request_host_devices`, or ``XLA_FLAGS=
    --xla_force_host_platform_device_count=N`` as tests/conftest.py
    pins 8).  ``n_pod > 0`` builds the multi-pod ("pod", "data",
    "model") axes so the ("pod", "data") FSDP/collective paths are
    exercisable on CPU.
    """
    shape = ((n_pod,) if n_pod else ()) + (n_data, n_model)
    axes = (("pod",) if n_pod else ()) + ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        platform = devices[0].platform
        hint = (" — ask for fake host devices before jax starts "
                "(launch/mesh.request_host_devices)"
                if platform == "cpu" else "")
        raise RuntimeError(f"host mesh {shape} needs {n} devices, have "
                           f"{len(devices)} {platform} device(s){hint}")
    return jax.make_mesh(shape, axes, devices=devices[:n])


def mesh_axes(mesh: Mesh) -> MeshAxes:
    if "pod" in mesh.axis_names:
        return MeshAxes(data=("pod", "data"), model="model")
    return MeshAxes(data=("data",), model="model")
