"""Mesh construction for twin-fleet serving and the LM dry-run.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialisation).

Twin serving (the primary workload — see
:mod:`repro.launch.fleet_serving`) uses a 1-D mesh over the ``"twins"``
axis: the trained weights are replicated onto every device and the fleet
(initial conditions + per-twin stimulus parameters) is sharded, so each
device rolls out its slice of the assets with zero cross-device
communication during the solve.

The LM dry-run meshes are kept for the roofline study:
Single pod:  (16, 16)  ("data", "model")   = 256 chips (one v5e pod)
Multi-pod:   (2, 16, 16) ("pod", "data", "model") = 512 chips.

"pod" composes with "data" for batch/grad-sync (pure DP across pods —
the DCI-friendly axis); "model" carries TP/EP within a pod (ICI).
"""
from __future__ import annotations

from typing import Optional

import jax

TWIN_AXIS = "twins"


def make_twin_mesh(n_devices: Optional[int] = None) -> jax.sharding.Mesh:
    """1-D mesh over the ``"twins"`` axis for fleet serving.

    ``n_devices=None`` uses every visible device (a single-host CPU run
    gets the trivial 1-device mesh and the sharded path degenerates to
    the single-device program — same numerics, same code).
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"make_twin_mesh: asked for {n} devices, have {len(devs)}")
    # Auto axes: the serving shard_map closes over the replicated
    # weights, which jax refuses for inputs sharded on Explicit axes
    # (make_mesh's default).
    return jax.make_mesh((n,), (TWIN_AXIS,), devices=devs[:n],
                         axis_types=(jax.sharding.AxisType.Auto,))


def twin_shard_count(mesh) -> int:
    """How many ways the twin axis is split on ``mesh`` (1 if absent)."""
    return int(mesh.shape.get(TWIN_AXIS, 1))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many (host) devices exist — for tests."""
    return jax.make_mesh((n_data, n_model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """The mesh axes that jointly shard the batch dimension."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]
