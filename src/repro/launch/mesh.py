"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state. Single pod:
(data=16, model=16) = 256 chips (TPU v5e pod). Multi-pod adds a leading
"pod" axis: (pod=2, data=16, model=16) = 512 chips.
"""
from __future__ import annotations

import os

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # REPRO_TEST_MESH="2x4" shrinks the mesh for CI smoke runs of the
    # dry-run machinery; production paths never set it.
    override = os.environ.get("REPRO_TEST_MESH")
    if override:
        dm = tuple(int(x) for x in override.split("x"))
        shape = ((2,) + dm) if multi_pod else dm
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / reduced dry-runs). Every axis is ``Auto``:
    the model code places activations with ``with_sharding_constraint``,
    which only accepts Auto axes."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# TPU v5e hardware constants (per chip) — roofline denominators.
PEAK_BF16_FLOPS = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
