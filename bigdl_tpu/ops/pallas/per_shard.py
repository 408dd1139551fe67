"""Run a Mosaic kernel once per device shard under a multi-device mesh.

XLA's SPMD partitioner cannot split a Mosaic custom call, and jax
refuses to lower one inside a partitioned program ("Mosaic kernels
cannot be automatically partitioned. Please wrap the call in a
shard_map."). The ring/Ulysses paths already call the kernels inside
their own ``shard_map``; the two bare dispatch sites — the attention
core (parallel/sequence.py) and LRN (nn/normalization.py) — come here.

The mesh is jax's own ambient one (``jax.set_mesh``; DistriOptimizer
traces its steps under it): a trace with no ambient mesh, a one-device
mesh, or one already inside a ``shard_map`` (manual axes) calls the
kernel bare, exactly as before.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["KernelShards", "kernel_shards"]


class KernelShards(NamedTuple):
    """How one kernel call splits over the ambient mesh: the partition
    spec shared by its array arguments and its output, and the shape
    each device sees (what the kernel's ``*_supported`` predicate and
    tile pickers must judge)."""

    spec: P
    local_shape: tuple

    def run(self, fn, *args):
        """``fn(*args)`` per device shard; every argument and the output
        carry ``spec``. Mesh axes the spec does not name see replicated
        operands and repeat the work — correct, never gathered into one
        oversized kernel call."""
        return jax.shard_map(fn, in_specs=(self.spec,) * len(args),
                             out_specs=self.spec, check_vma=False)(*args)


def kernel_shards(shape, *, head_dim: int | None = None
                  ) -> KernelShards | None:
    """The split for a kernel whose arguments are shaped ``shape``:
    dim 0 (batch) over the mesh ``data`` axis and, when given,
    ``head_dim`` over the ``model`` axis (tensor-parallel q/k/v are
    head-sharded there already) — each only where it divides. ``None``
    when the trace is not under a partitioner: call the kernel bare."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.manual_axes or math.prod(mesh.shape.values()) == 1:
        return None
    entries: list = [None] * len(shape)
    wanted = [(0, "data")]
    if head_dim is not None:
        wanted.append((head_dim, "model"))
    local = list(shape)
    for dim, axis in wanted:
        n = mesh.shape.get(axis, 1)
        if n > 1 and shape[dim] % n == 0:
            entries[dim] = axis
            local[dim] = shape[dim] // n
    return KernelShards(P(*entries), tuple(local))
