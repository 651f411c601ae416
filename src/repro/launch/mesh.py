"""Production meshes (TPU v5e target).

Defined as FUNCTIONS so importing this module never touches jax device
state; ``launch/dryrun.py`` sets --xla_force_host_platform_device_count=512
before any jax import to make these constructible on the CPU host.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU tests/benches (1 data x 1 model)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_abstract_mesh(shape, axes):
    """Device-free mesh for sharding-rule tests."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))


# TPU v5e hardware constants for the roofline model (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW_PER_LINK = 50e9            # B/s per link
