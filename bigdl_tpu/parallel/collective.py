"""Collective communication primitives over the device mesh.

This is the TPU-native replacement for the reference's hand-rolled
BlockManager communication backend (parameters/AllReduceParameter.scala:53-229
— reduce-scatter of gradient slices + all-gather of weight slices through a
KV store, SURVEY §2.6/§5.8). Here each collective is an XLA op over a named
mesh axis, laid onto ICI (within a slice) or DCN (across slices) by the
compiler; the helpers wrap ``shard_map`` so callers can run collectives
eagerly (outside a jit) or compose them inside one.

The wire-compression parity point: the reference compresses f32 to "fp16" by
truncating to the TOP 16 BITS of the IEEE float (FP16CompressedTensor.scala:
267-275) — that bit pattern IS bfloat16. So ``wire_dtype=jnp.bfloat16``
reproduces the reference's wire format exactly, natively on TPU.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map as _shard_map

from bigdl_tpu.parallel.engine import get_mesh


def shard_map(f, *, mesh, in_specs, out_specs, check_rep=False):
    """``jax.shard_map`` under the repo's ``check_rep`` spelling."""
    return _shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=check_rep)


__all__ = ["all_reduce", "all_gather", "reduce_scatter", "ppermute",
           "all_to_all", "psum_tree", "pmean_tree",
           "process_allgather_pyobj"]


_MAX_PYOBJ_PAYLOAD = 2 ** 31


def _check_payload_size(n_bytes: int) -> None:
    """The size gather rides jax arrays, which truncate int64 to int32
    when x64 is off (the default) — a >= 2 GiB pickle would overflow
    silently and corrupt the unpickle slicing. Refuse loudly instead
    (ADVICE.md)."""
    if n_bytes >= _MAX_PYOBJ_PAYLOAD:
        raise ValueError(
            f"process_allgather_pyobj payload of {n_bytes} bytes "
            f"meets/exceeds the int32 size-gather limit "
            f"({_MAX_PYOBJ_PAYLOAD - 1} bytes) — shard the object "
            "across several gathers")


def process_allgather_pyobj(obj):
    """Gather one arbitrary (picklable) python object per PROCESS; every
    process returns the list ordered by process index.

    The host-side control-plane counterpart to the in-step collectives
    above — the role Spark's driver-side reduce/accumulators played in
    the reference (Metrics.scala:24-27, DistriValidator.scala:29-80).
    COLLECTIVE over the jax.distributed job: every process must call it
    at the same point. Single-process: returns ``[obj]`` without
    touching the backend. Objects differ in size per process, so lengths
    are gathered first and payloads padded to the max."""
    import pickle

    import numpy as np

    if jax.process_count() == 1:
        return [obj]
    from jax.experimental import multihost_utils

    payload = np.frombuffer(pickle.dumps(obj), np.uint8)
    _check_payload_size(payload.size)
    sizes = multihost_utils.process_allgather(
        np.asarray([payload.size], np.int64))
    buf = np.zeros(int(sizes.max()), np.uint8)
    buf[:payload.size] = payload
    bufs = multihost_utils.process_allgather(buf)
    return [pickle.loads(bufs[p, :int(sizes[p])].tobytes())
            for p in range(bufs.shape[0])]


def _wire(x, wire_dtype):
    return x.astype(wire_dtype) if wire_dtype is not None else x


def _resolve_codec(codec):
    from bigdl_tpu.parameters.compression import get_codec
    return get_codec(codec)


def _compressed_scatter_body(v, axis, n, codec, key, mean):
    """Per-shard body of a wire-compressed reduce-scatter: quantize my
    full contribution per destination slice, exchange int8/uint16
    payloads with ``all_to_all`` (the wire stays at codec width — a
    psum would have to upcast to accumulate), decode the N received
    contributions and sum locally. Returns my f32 slice."""
    rows = v.reshape(n, -1)                  # row j = my payload for shard j
    enc = codec.encode(rows, key)
    got = {k: jax.lax.all_to_all(p if p.ndim > 1 else p[:, None], axis,
                                 split_axis=0, concat_axis=0, tiled=False)
           for k, p in enc.items()}
    got = {k: (p if enc[k].ndim > 1 else p[..., 0]) for k, p in got.items()}
    out = jnp.sum(codec.decode(got), axis=0)
    return out / n if mean else out


def all_reduce(x, axis: str = "data", mesh: Mesh | None = None, *,
               mean: bool = False, wire_dtype=None):
    """Reduce N per-shard contributions across ``axis``.

    ``x`` is the STACK of contributions: leading dim == mesh.shape[axis],
    ``x[i]`` being what shard ``i`` contributes (the eager emulation of N
    parties each calling the collective with their own value). Returns the
    elementwise sum (or mean) of the blocks, shape ``x.shape[1:]``,
    replicated on every shard.

    Equivalent of the reference's putGradients+aggregate+getWeights round
    trip collapsed into one ``lax.psum``. A replicated input with
    ``in_specs=P()`` would make psum count the same value N times — the
    stacked contract keeps the sum honest.
    """
    mesh = mesh or get_mesh()
    n = mesh.shape[axis]
    if x.ndim == 0 or x.shape[0] != n:
        raise ValueError(
            f"all_reduce wants stacked per-shard contributions: leading dim "
            f"{x.shape[0] if x.ndim else '<scalar>'} != mesh axis "
            f"'{axis}' size {n}")
    orig_dtype = x.dtype

    def body(v):
        v = _wire(v[0], wire_dtype)
        out = jax.lax.pmean(v, axis) if mean else jax.lax.psum(v, axis)
        return out.astype(orig_dtype)

    return shard_map(body, mesh=mesh, in_specs=(P(axis),), out_specs=P(),
                     check_rep=False)(x)


def psum_tree(tree, axis: str = "data", mesh: Mesh | None = None, *,
              mean: bool = False, wire_dtype=None):
    """all_reduce over every leaf of a pytree; each leaf carries the stacked
    per-shard leading dim (flat-gradient equivalent)."""
    return jax.tree.map(
        lambda v: all_reduce(v, axis, mesh, mean=mean,
                             wire_dtype=wire_dtype), tree)


def pmean_tree(tree, axis: str = "data", mesh: Mesh | None = None, *,
               wire_dtype=None):
    return psum_tree(tree, axis, mesh, mean=True, wire_dtype=wire_dtype)


def all_gather(x, axis: str = "data", mesh: Mesh | None = None,
               concat_axis: int = 0, *, codec=None):
    """Each shard contributes its block; all get the concatenation
    (reference AllReduceParameter.getWeights, :134-159).

    ``codec`` (a name from ``parameters.compression.KNOWN_CODECS`` or a
    ``WireCodec``) compresses the payload on the wire — the reference's
    FP16 ``getWeights`` is ``codec="bf16"``. Each shard's whole block is
    one codec row (one scale for int8). Requires f32 input."""
    mesh = mesh or get_mesh()
    codec = _resolve_codec(codec)

    def body(v):
        if codec is not None and codec.name != "fp32":
            enc = codec.encode(v.reshape(1, -1))
            got = {k: jax.lax.all_gather(p, axis, tiled=True)
                   for k, p in enc.items()}
            out = codec.decode(got).reshape((-1,) + tuple(v.shape[1:]))
        else:
            out = jax.lax.all_gather(v, axis, tiled=True)
        if concat_axis != 0:
            out = jnp.moveaxis(out, 0, concat_axis)
        return out

    return shard_map(body, mesh=mesh, in_specs=(P(axis),), out_specs=P(),
                     check_rep=False)(x)


def reduce_scatter(x, axis: str = "data", mesh: Mesh | None = None, *,
                   mean: bool = False, wire_dtype=None, codec=None,
                   key=None):
    """Sum N per-shard contributions; each shard keeps its slice (reference
    putGradients + aggregrateGradientPartition, :161-215).

    ``x`` is the stack of contributions, shape ``(N, S, ...)`` with
    ``N == mesh.shape[axis]`` — shard ``i`` contributes ``x[i]``. Returns
    the elementwise sum (or mean), shape ``(S, ...)``, sharded over dim 0
    along ``axis`` (each shard owns ``S/N`` rows).

    ``codec`` compresses the WIRE: each shard quantizes its contribution
    per destination slice, slices ride an ``all_to_all`` at codec width,
    and the owner decodes + sums in f32 (a ``psum_scatter`` would have to
    upcast to accumulate — this construction keeps the payload at wire
    width end to end). ``key`` enables stochastic rounding for codecs
    that support it; requires ``S`` divisible by the axis size and rank-1
    slices."""
    mesh = mesh or get_mesh()
    n = mesh.shape[axis]
    if x.ndim == 0 or x.shape[0] != n:
        raise ValueError(
            f"reduce_scatter wants stacked per-shard contributions: leading "
            f"dim {x.shape[0] if x.ndim else '<scalar>'} != mesh axis "
            f"'{axis}' size {n}")
    orig_dtype = x.dtype
    codec = _resolve_codec(codec)
    if codec is not None and codec.name != "fp32":
        if x.ndim != 2:
            raise ValueError(
                "compressed reduce_scatter wants (N, S) stacked flat "
                f"contributions, got rank {x.ndim}")
        if x.shape[1] % n != 0:
            raise ValueError(
                f"compressed reduce_scatter needs S divisible by the "
                f"axis size: {x.shape[1]} % {n} != 0 (pad first — "
                "AllReduceParameter.put_gradients does)")

        def cbody(v, k):
            return _compressed_scatter_body(v[0], axis, n, codec,
                                            k, mean).astype(orig_dtype)

        if key is None:
            body = lambda v: cbody(v, None)
            return shard_map(body, mesh=mesh, in_specs=(P(axis),),
                             out_specs=P(axis), check_rep=False)(x)
        # distinct stochastic-rounding stream per shard
        body = lambda v, k: cbody(
            v, jax.random.fold_in(k, jax.lax.axis_index(axis)))
        return shard_map(body, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=P(axis), check_rep=False)(x, key)

    def body(v):
        v = _wire(v[0], wire_dtype)
        out = jax.lax.psum_scatter(v, axis, scatter_dimension=0, tiled=True)
        if mean:
            out = out / n
        return out.astype(orig_dtype)

    return shard_map(body, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis),
                     check_rep=False)(x)


def ppermute(x, perm, axis: str = "data", mesh: Mesh | None = None):
    """Point-to-point ring shift (ring-attention building block).

    ``perm`` is a list of (src, dst) pairs over the axis indices.
    """
    mesh = mesh or get_mesh()
    return shard_map(
        lambda v: jax.lax.ppermute(v, axis, perm),
        mesh=mesh, in_specs=(P(axis),), out_specs=P(axis),
        check_rep=False)(x)


def all_to_all(x, axis: str = "data", mesh: Mesh | None = None, *,
               split_axis: int = 1, concat_axis: int = 0):
    """Transpose shard ownership between two tensor dims (DeepSpeed-Ulysses
    style sequence<->head exchange)."""
    mesh = mesh or get_mesh()

    def body(v):
        return jax.lax.all_to_all(v, axis, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)

    return shard_map(body, mesh=mesh, in_specs=(P(axis),),
                     out_specs=P(axis), check_rep=False)(x)
