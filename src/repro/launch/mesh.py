"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches JAX device state.  The production target is TPU v5e:
one pod = 16 x 16 = 256 chips; the multi-pod config stacks 2 pods (512
chips) along a leading 'pod' axis used for data parallelism and for the
collaborative-intelligence edge/cloud split (see split_runtime).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """Mesh whose axes are all ``Auto``: GSPMD places what the code does
    not pin, and ``with_sharding_constraint`` hints (``models.context.
    constrain``) may name any axis, also inside a shard_map region that
    is manual over other axes.  ``jax.make_mesh`` defaults to
    ``Explicit`` axes, which reject such hints."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(devices: int | None = None, model_axis: int | None = None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = devices or len(jax.devices())
    m = model_axis or (2 if n % 2 == 0 and n > 1 else 1)
    return make_mesh((n // m, m), ("data", "model"))


def dp_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
