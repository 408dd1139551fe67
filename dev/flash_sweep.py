"""Time flash attention's schedules on the chip, one table a shape.

``chiprun -- python3 dev/flash_sweep.py [--parent FILE]`` walks
``tuning.autotuner.flash_candidates`` for causal self-attention at the
benchmark cells' per-chip shape (128 heads of 2048 x 64, bf16) and at
head width 128, in the ways ``ops/pallas/flash_attention.py`` can run
them — looped over a head in VMEM (a pair is then the q rows a grid step
takes and the K rows a loop step walks), looped with the block on the
diagonal left uncut, looped forward with the two-kernel backward,
everything streamed over the grid — plus one non-causal call, and prints
forward and backward milliseconds for each pair. The static menu in that
module is written from this table (PERF.md, PR 26). ``--parent`` names
another copy of the module (the parent commit's) to time beside it.
Every candidate's outputs and gradients are compared with the first one
measured at its shape.

A pair reaches the kernels the way a measured winner would: as a tuning
record, which ``_tuned_blocks`` prefers to the menu. The way of running
is forced through the module's VMEM budget and its count of cuts.
Neither is a switch of the program: no record file ships.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# (B, S, H, D): the benchmark cells' shape a chip (4 sequences x 32
# heads), and head width 128 (opt-6.7b's) at the same score count
CELL_SHAPE = (4, 2048, 32, 64)
WIDE_SHAPE = (2, 2048, 32, 128)
# (way, bq, bk): looped kernels take nested pairs, smaller first, up to 8
# blocks a step; the grid kernels ran every pair of (256, 512) x (256,
# 512, 1024) in this PR's first sweep and keep the best two as anchors
WAYS = (("looped", (128, 256, 512), (256, 512, 1024, 2048)),
        ("looped_uncut", (512,), (1024, 2048)),
        ("looped+two_kernel", (512,), (512, 1024)),
        ("streamed", (512,), (512, 1024)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _time(fn, args, iters):
    import jax
    out = fn(*args)                         # compiles
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3, out


def _worst(got, want):
    import jax.numpy as jnp
    f32 = jnp.float32
    return max(float(jnp.max(jnp.abs(g.astype(f32) - w.astype(f32)))
                     / jnp.max(jnp.abs(w.astype(f32))))
               for g, w in zip(got, want))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="another flash_attention.py to time")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default="chiprun_out/flash_sweep.json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.observability import trace
    from bigdl_tpu.ops.pallas import flash_attention as fa
    from bigdl_tpu.tuning.autotuner import flash_candidates, flash_est_vmem
    from bigdl_tpu.tuning.records import TuningRecords, set_default_records

    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind!r}",
          flush=True)
    if dev.platform != "tpu":
        print("flash_sweep: no TPU, and a time from anything else is "
              "not a result", file=sys.stderr)
        return 2
    modules = {"change": fa}
    if args.parent:
        modules["parent"] = _load(args.parent, "parent_flash_attention")
    rows = []
    tracer = trace.get_tracer()

    def measure(label, mod, shape, causal, ref):
        b, s, h, d = shape
        rs = np.random.default_rng(0)
        q, k, v, ct = (jnp.asarray(0.5 * rs.standard_normal(shape),
                                   jnp.bfloat16) for _ in range(4))

        def fwd(q, k, v):
            return mod.flash_attention(q, k, v, causal=causal)

        def both(q, k, v):
            o, vjp = jax.vjp(fwd, q, k, v)
            return (o,) + vjp(ct)

        row = dict(label)
        seen = []           # the schedules stated while this row traced

        def tap(ev):
            if ev["name"] == "flash_schedule":
                a = ev["args"]
                seen.append([a["bq"], a["bk"], a["bwd_bq"], a["bwd_bk"],
                             a["block"]])

        tracer.add_tap(tap)
        try:
            row["fwd_ms"], _ = _time(jax.jit(fwd), (q, k, v), args.iters)
            total, out = _time(jax.jit(both), (q, k, v), args.iters)
            row["bwd_ms"] = total - row["fwd_ms"]
            if ref:
                row["worst_vs_first"] = _worst(out, ref[0])
            else:
                ref.append(out)
        except Exception as e:      # a refused tile is a row, not the end
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        finally:
            tracer.remove_tap(tap)
        row["stated"] = seen
        print(json.dumps(row), flush=True)
        rows.append(row)

    def sweep(shape, ways):
        b, s, h, d = shape
        ref: list = []
        if "parent" in modules:
            set_default_records(TuningRecords())
            measure({"shape": shape, "causal": True, "module": "parent"},
                    modules["parent"], shape, True, ref)
        est = flash_est_vmem(d, sq=s)
        lanes = -(-d // 128) * 128
        # way -> (VMEM budget, cuts of the block on the diagonal); the
        # two-kernel backward's budget lies between what the forward
        # holds of a head (8 s lanes bytes) and the backward (24)
        forced = {"looped": (fa._RESIDENT_BUDGET, fa._DIAGONAL_CUTS),
                  "looped_uncut": (fa._RESIDENT_BUDGET, 1),
                  "looped+two_kernel": (10 * s * lanes, fa._DIAGONAL_CUTS),
                  "streamed": (0, fa._DIAGONAL_CUTS)}
        for way, q_sizes, k_sizes in ways:
            for c in flash_candidates(s, s, k_cap=2048):
                if c["bq"] not in q_sizes or c["bk"] not in k_sizes:
                    continue
                if way in ("looped", "looped_uncut") and not (
                        c["bk"] % c["bq"] == 0 and c["bk"] <= 8 * c["bq"]):
                    continue
                store = TuningRecords()
                store.record("flash_attention", {"sq": s, "skv": s}, c)
                set_default_records(store)
                old = fa._RESIDENT_BUDGET, fa._DIAGONAL_CUTS
                fa._RESIDENT_BUDGET, fa._DIAGONAL_CUTS = forced[way]
                try:
                    sched = fa._schedule(True, s, s, d, 2)
                    measure({"shape": shape, "causal": True,
                             "module": "change", "way": way, **c,
                             "kv_resident": sched.kv_resident,
                             "one_pass_backward": sched.one_pass_backward,
                             "waste": sched.tiles_computed
                             / sched.tiles_visible,
                             "est_vmem_mb": est(c) / 2 ** 20},
                            fa, shape, True, ref)
                finally:
                    fa._RESIDENT_BUDGET, fa._DIAGONAL_CUTS = old
        set_default_records(TuningRecords())
        measure({"shape": shape, "causal": True, "module": "change",
                 "way": "menu", **fa._schedule(True, s, s, d, 2)._asdict()},
                fa, shape, True, ref)

    sweep(CELL_SHAPE, WAYS)
    sweep(WIDE_SHAPE, WAYS[:1])
    # no diagonal: rectangular tiles, and only the scale fold differs
    ref: list = []
    set_default_records(TuningRecords())
    for name, mod in reversed(modules.items()):
        measure({"shape": CELL_SHAPE, "causal": False, "module": name},
                mod, CELL_SHAPE, False, ref)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
