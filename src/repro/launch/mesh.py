"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips.

    REPRO_DRYRUN_MESH="d,m" overrides the single-pod extents (test-only;
    the production dry-run never sets it)."""
    import os
    override = os.environ.get("REPRO_DRYRUN_MESH")
    if override:
        d, m = (int(x) for x in override.split(","))
    else:
        d, m = 16, 16
    shape = (2, d, m) if multi_pod else (d, m)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the LM stack places activations with sharding constraints,
    # which jax.make_mesh's default Explicit axes turn into assertions
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def mesh_axes_dict(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
