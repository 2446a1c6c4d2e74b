"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; the multi-pod mesh adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small CPU mesh for tests/examples (requires forced host devices)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def rules_for(mesh, kind: str = "train"):
    from repro.parallel.sharding import (DECODE_RULES, DECODE_RULES_MULTI,
                                         MULTI_POD_RULES, SINGLE_POD_RULES)
    multi = "pod" in mesh.shape
    if kind == "decode":  # weights-stationary serving layout (§Perf iter 1)
        return DECODE_RULES_MULTI if multi else DECODE_RULES
    return MULTI_POD_RULES if multi else SINGLE_POD_RULES
