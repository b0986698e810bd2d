"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets
``--xla_force_host_platform_device_count=512`` before first jax init.

Axes:
  single-pod : (16, 16)      -> ('data', 'model')        = 256 chips
  multi-pod  : (2, 16, 16)   -> ('pod', 'data', 'model') = 512 chips

'pod' composes with 'data' for the batch dimension (DP across pods — the
gradient all-reduce crossing 'pod' is the DCN-equivalent hop in a real
deployment) and with FSDP parameter sharding for the >=52B archs.
"""

from __future__ import annotations

import math
from typing import Tuple

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape: Tuple[int, ...] = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under launch/dryrun.py (sets "
            "--xla_force_host_platform_device_count=512)")
    # more devices than the mesh needs (e.g. 512 forced, single-pod 256)
    return Mesh(np.array(devices[:n]).reshape(shape), axes)


def make_smoke_mesh(model_axis: int = 1) -> Mesh:
    """Tiny mesh over whatever devices exist (tests / examples)."""
    devices = jax.devices()
    n = len(devices)
    assert n % model_axis == 0
    return Mesh(np.array(devices).reshape(n // model_axis, model_axis),
                ("data", "model"))


def make_serving_mesh(shape) -> "Mesh | None":
    """Mesh for ``ServeEngine(mesh=...)`` from a shape spec.

    ``shape``: None (single-device engine, returns None), an int or
    1-tuple (pure tensor parallel: axis ('model',)), or a 2-tuple
    (('data', 'model') — slots over 'data', heads/vocab over 'model').
    Also accepts a "2x2"-style string (the CLI/benchmark ``--mesh``
    flag).  ``jax.make_mesh`` lays the first prod(shape) devices out on
    the chips' physical topology, so it composes with
    ``--xla_force_host_platform_device_count``.  Axes are ``Auto``: the
    engine places every array itself."""
    if shape is None:
        return None
    if isinstance(shape, str):
        shape = tuple(int(p) for p in shape.lower().split("x"))
    if isinstance(shape, int):
        shape = (shape,)
    shape = tuple(int(s) for s in shape)
    if len(shape) not in (1, 2):
        raise ValueError(f"serving mesh shape must be 1-D or 2-D, "
                         f"got {shape}")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for serving mesh {shape}, have "
            f"{len(devices)} — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} (before jax "
            f"initializes) or shrink the mesh")
    axes = ("model",) if len(shape) == 1 else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices[:n])
