"""Collective-bandwidth accounting and microbenchmark.

The second BASELINE.json metric is "DistriOptimizer allreduce GB/s". The
reference instruments its aggregation path end-to-end — put/get-gradient
phase timers around the BlockManager reduce-scatter/all-gather
(parameters/AllReduceParameter.scala:134-228, phase metrics at
optim/DistriOptimizer.scala:113-117,172-174,211). Under XLA the gradient
allreduce fuses INTO the compiled step, so the equivalent instrumentation
is:

1. :func:`collective_bytes` — static accounting: parse the compiled step's
   HLO for collective ops and report logical bytes plus the per-chip wire
   bytes a ring schedule moves (all-reduce: 2B(N-1)/N send+recv per chip).
   DistriOptimizer records these in its Metrics every run.
2. :func:`allreduce_bench` — a timed psum microbenchmark at a chosen size
   (default: the Inception-v1 flat gradient, ~13M params) over the mesh's
   ``data`` axis. Reports algorithmic bandwidth (logical bytes / time) and
   bus bandwidth (wire bytes / time — the number NCCL-style harnesses
   quote). On the 8-virtual-CPU-device mesh it validates shape/compile so
   a pod run is one command:

       python -m bigdl_tpu.parallel.collective_bench --sizeMB 54

Cross-check: on one real chip the data axis is 1 and no collective is
emitted — both paths report zero collectives rather than a fake number.
"""
from __future__ import annotations

import re
import time

import numpy as np

__all__ = ["collective_bytes", "allreduce_bench"]

_DT_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
             "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
             "pred": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")

# per-chip wire traffic of a ring schedule, as a multiple of the logical
# payload B over N participants (send+recv counted once — the number a
# bus-bandwidth benchmark divides by)
_WIRE_FACTOR = {
    "all-reduce": lambda n: 2.0 * (n - 1) / n,
    "all-gather": lambda n: (n - 1) / n,
    "reduce-scatter": lambda n: (n - 1) / n,
    "collective-permute": lambda n: 1.0,
    "all-to-all": lambda n: (n - 1) / n,
}


def _element_bytes(shape_str: str) -> list[int]:
    out = []
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape_str):
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        out.append(n * _DT_BYTES.get(m.group(1), 4))
    return out


def _payload_bytes(shape_str: str, async_start: bool) -> int:
    """Collective payload from an instruction's result shape.

    Async ``-start`` ops carry a tuple of (operand(s), result, ...);
    summing it double-counts the payload (all-reduce-start holds two
    full-size copies). The LARGEST element is the right basis for every
    kind: all-reduce operand==result, all-gather's output and
    reduce-scatter's input are the wire-formula operands and are the
    biggest members."""
    elems = _element_bytes(shape_str)
    if not elems:
        return 0
    return max(elems) if async_start else sum(elems)


def _group_size(line: str, default: int) -> int:
    # replica_groups={{0,1,2,3}} or replica_groups=[2,4]<=[8] forms
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        return len([t for t in m.group(1).split(",") if t.strip() != ""])
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    return default


def collective_bytes(hlo_text: str, n_devices: int) -> dict:
    """Account every collective in an optimized HLO module.

    Returns ``{"ops": count, "logical_bytes": B, "wire_bytes_per_chip": W,
    "by_kind": {kind: [count, logical_bytes]}}``. ``start`` variants
    (async collectives) are counted once; ``done`` halves are skipped.
    """
    ops = 0
    logical = 0.0
    wire = 0.0
    by_kind: dict[str, list] = {}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(
            r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*((?:\([^)]*\)|[\w\[\],{}: ()]+?))"
            r"\s+([\w\-]+)\(", ls)
        if not m:
            continue
        op = m.group(2)
        base = op.removesuffix("-start")
        if base not in _COLLECTIVES or op.endswith("-done"):
            continue
        b = _payload_bytes(m.group(1), op.endswith("-start"))
        n = max(_group_size(ls, n_devices), 1)
        ops += 1
        logical += b
        wire += b * _WIRE_FACTOR[base](n)
        k = by_kind.setdefault(base, [0, 0.0])
        k[0] += 1
        k[1] += b
    return {"ops": ops, "logical_bytes": logical,
            "wire_bytes_per_chip": wire, "by_kind": by_kind}


def allreduce_bench(size_mb: float = 54.0, dtype="float32",
                    iters: int = 20, warmup: int = 3, mesh=None,
                    axis: str = "data") -> dict:
    """Timed gradient-sized allreduce over a mesh axis.

    Every device contributes its own distinct buffer (as in sync-SGD) and
    receives the sum — a ``lax.psum`` under ``shard_map``, the exact
    collective DistriOptimizer's backward emits. Default size is the
    Inception-v1 flat f32 gradient (BASELINE.md headline config).
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bigdl_tpu.parallel.engine import get_mesh

    mesh = mesh or get_mesh()
    n = int(mesh.shape[axis])
    dtype = jnp.dtype(dtype)
    length = max(int(size_mb * 1e6 / dtype.itemsize), 1)
    # pad to lanes so the wire payload is the intended size
    length = -(-length // 128) * 128
    host = np.random.default_rng(0)
    x = jnp.asarray(
        host.standard_normal((n, length)).astype(np.float32)).astype(dtype)
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))

    @jax.jit
    def step(x):
        def block(xs):           # xs: (1, length) — this device's gradient
            return jax.lax.psum(xs, axis)

        return shard_map(block, mesh=mesh, in_specs=P(axis),
                         out_specs=P(axis))(x)

    out = step(x)
    jax.block_until_ready(out)
    for _ in range(warmup):
        out = step(x)
    np.asarray(jax.tree.leaves(out)[0][0, 0])   # host read: waits for it
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(x)
    np.asarray(jax.tree.leaves(out)[0][0, 0])
    dt = (time.perf_counter() - t0) / iters

    logical = length * dtype.itemsize
    wire = logical * _WIRE_FACTOR["all-reduce"](n) if n > 1 else 0.0
    out = {
        "metric": "allreduce_bus_bandwidth",
        "devices": n,
        "payload_mb": round(logical / 1e6, 3),
        "dtype": str(dtype),
        "time_ms": round(dt * 1e3, 4),
        "alg_gbps": round(logical / dt / 1e9, 3),
        "bus_gbps": round(wire / dt / 1e9, 3),
        "unit": "GB/s",
    }
    # compile/memory telemetry of the benchmarked executable — the
    # lower().compile() is a cache hit after the timed loop above
    from bigdl_tpu.observability import compile_watch
    try:
        compile_watch.record_executable(
            "collective_bench_allreduce", step.lower(x).compile())
    except Exception:               # telemetry must never fail a bench
        pass

    # export through the process-wide registry so the microbenchmark
    # lands on the same Prometheus/JSON surface as training metrics
    from bigdl_tpu.observability.registry import default_registry
    reg = default_registry()
    lbl = {"dtype": str(dtype), "devices": str(n)}
    names = ("dtype", "devices")
    reg.gauge("collective_bench_alg_gbps",
              "allreduce algorithmic bandwidth (logical bytes / time)",
              labelnames=names).set(out["alg_gbps"], **lbl)
    reg.gauge("collective_bench_bus_gbps",
              "allreduce bus bandwidth (ring wire bytes / time)",
              labelnames=names).set(out["bus_gbps"], **lbl)
    reg.gauge("collective_bench_time_ms",
              "allreduce mean iteration wall clock",
              labelnames=names).set(out["time_ms"], **lbl)
    return out


def main(argv=None):
    import argparse
    import json

    p = argparse.ArgumentParser(
        description="Gradient-allreduce bandwidth microbenchmark "
                    "(BASELINE.json second metric)")
    p.add_argument("--sizeMB", type=float, default=54.0,
                   help="payload size (54 = Inception-v1 f32 flat grad)")
    p.add_argument("--dtype", default="float32",
                   help="payload dtype (bfloat16 = the bf16-wire path)")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--dataParallel", type=int, default=None,
                   help="mesh size (default: all visible devices)")
    args = p.parse_args(argv)

    import jax

    from bigdl_tpu.parallel.engine import Engine
    if args.dataParallel:
        devices = jax.devices()
        if len(devices) < args.dataParallel:
            # never substitute virtual CPU devices: the bandwidth
            # printed below would not be the interconnect's
            raise SystemExit(
                f"--dataParallel {args.dataParallel} but only "
                f"{len(devices)} {devices[0].platform} device(s) are "
                "visible")
        Engine.init(axes={"data": args.dataParallel},
                    devices=devices[:args.dataParallel])
    print(json.dumps(allreduce_bench(args.sizeMB, args.dtype, args.iters)))


if __name__ == "__main__":
    main()
