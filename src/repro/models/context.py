"""Distribution context threaded through model code."""

from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass(frozen=True)
class DistContext:
    """Mesh + axis-name conventions.  ``None`` context = single device.

    dp_axes: axes the batch is sharded over (('pod','data') or ('data',)).
    tp_axis: tensor/expert-parallel axis ('model').
    """

    mesh: Any = None
    dp_axes: tuple[str, ...] = ("data",)
    tp_axis: str = "model"

    @property
    def tp_size(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.shape.get(self.tp_axis, 1)

    @property
    def dp_size(self) -> int:
        if self.mesh is None:
            return 1
        out = 1
        for a in self.dp_axes:
            out *= self.mesh.shape.get(a, 1)
        return out


def constrain(x, ctx: DistContext | None, *axes):
    """Pin activation sharding: axes entries are None, 'dp', or 'tp'.

    Anchoring activations (batch on the dp axes, head/ffn dims on the tp
    axis) at layer boundaries is what forces GSPMD to resolve FSDP weight
    contractions by all-gathering the (small) weights instead of
    replicating the (large) activations.  Divisibility-checked: any axis
    that does not divide falls back to unsharded.
    """
    if ctx is None or ctx.mesh is None:
        return x
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = []
    used: set[str] = set()
    for dim, a in zip(x.shape, axes):
        # each axis group is assigned to the FIRST marked dim that divides,
        # so ('dp', None, 'tp', 'tp') = "heads if divisible, else head_dim"
        if a == "dp" and "dp" not in used and ctx.dp_size > 1 \
                and dim % ctx.dp_size == 0:
            spec.append(ctx.dp_axes)
            used.add("dp")
        elif a == "tp" and "tp" not in used and ctx.tp_size > 1 \
                and dim % ctx.tp_size == 0:
            spec.append(ctx.tp_axis)
            used.add("tp")
        else:
            spec.append(None)
    spec += [None] * (len(x.shape) - len(spec))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*spec)))


def shard_map(body, mesh, in_specs, out_specs, *, manual_axes=None):
    """The repo's one shard_map entry point.

    ``manual_axes=None`` maps fully manually over every mesh axis.  A
    set (e.g. ``{'pod'}``) makes a partially-manual region with the
    remaining axes left automatic (the mesh's axes must be ``Auto``; see
    ``launch.mesh.make_mesh``).
    """
    import jax
    kwargs = {}
    if manual_axes is not None:
        kwargs = dict(axis_names=frozenset(manual_axes), check_vma=False)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
