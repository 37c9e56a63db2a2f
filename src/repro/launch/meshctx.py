"""Process-wide mesh context.

Model code (notably the expert-parallel MoE path) needs to know whether it is
running under a mesh and which axes mean "batch/data" vs "model/tensor".
Holding that in a context object keeps model code mesh-agnostic: with no mesh
set, everything runs the single-device local path (CPU smoke tests); with a
mesh set, MoE switches to an explicit shard_map expert-parallel schedule.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional, Tuple

import jax

# model code imports shard_map from here
shard_map = jax.shard_map


@dataclasses.dataclass(frozen=True)
class MeshContext:
    mesh: jax.sharding.Mesh
    data_axes: Tuple[str, ...]   # axes the batch is sharded over, e.g. ("pod","data")
    model_axis: str              # tensor/expert-parallel axis, e.g. "model"

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def data_size(self) -> int:
        out = 1
        for a in self.data_axes:
            out *= self.mesh.shape[a]
        return out


_CURRENT: Optional[MeshContext] = None


def current() -> Optional[MeshContext]:
    return _CURRENT


@contextlib.contextmanager
def use_mesh(ctx: Optional[MeshContext]):
    global _CURRENT
    prev = _CURRENT
    _CURRENT = ctx
    try:
        if ctx is not None:
            with ctx.mesh:
                yield ctx
        else:
            yield None
    finally:
        _CURRENT = prev
