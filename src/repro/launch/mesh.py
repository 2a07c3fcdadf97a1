"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — callers (and only callers) decide when the
backend initializes.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with auto-sharded axes (its default is explicit
    axes, which the GSPMD sharding rules here do not use)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod 16×16 = 256 chips, or 2 pods × 256 = 512 chips.

    Axes: ``data`` (DP + FSDP + long-context sequence sharding),
    ``model`` (TP / expert parallel / vocab sharding), and ``pod``
    (cross-pod data parallelism by default; the GPipe pipeline in
    `repro.distributed.pipeline` can claim it instead).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Tiny mesh over whatever devices the host actually has (tests)."""
    n = len(jax.devices())
    data = n // model_axis
    return make_mesh((data, model_axis), ("data", "model"))
