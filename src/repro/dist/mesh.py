"""Platform meshes: the MESH image layer resolved to devices.

``local``   -- every visible device on the data axis (dev laptops, CI, and
               the 1-CPU test environment);
``pod``     -- one 256-chip pod: 16-way data x 16-way model;
``multipod``-- two pods: pod x data x model = 2 x 16 x 16 (the dry-run's
               512-host-device mesh).

Batch ("replica") axes are ordered slow-to-fast as ("pod", "data"): pod is
the outermost / highest-latency dimension, which is what the hierarchical
grad reductions in core/abi.py rely on.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh

# platform name -> (axis names, mesh shape). A shape of None means "all
# visible devices on the data axis" (resolved at mesh-construction time, so
# importing this module never touches jax device state).
PLATFORMS: dict[str, dict] = {
    "local": {"axes": ("data", "model"), "shape": None},
    "pod": {"axes": ("data", "model"), "shape": (16, 16)},
    "multipod": {"axes": ("pod", "data", "model"), "shape": (2, 16, 16)},
}


def make_platform_mesh(platform: str = "local") -> Mesh:
    """Resolve a platform name into a concrete device mesh."""
    try:
        spec = PLATFORMS[platform]
    except KeyError:
        raise ValueError(
            f"unknown platform {platform!r}; expected one of {sorted(PLATFORMS)}"
        ) from None
    axes = spec["axes"]
    shape = spec["shape"] or (jax.device_count(), 1)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """The replica (data-parallel) axes of ``mesh``, ordered slow-to-fast."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
