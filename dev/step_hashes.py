#!/usr/bin/env python3
"""sha256 of each training cell's step program as lowered for a
described v5e (compile-only, no chip), to show that a change leaves a
cell's program as it was:

    JAX_PLATFORMS=cpu python3 dev/step_hashes.py [--root CHECKOUT] \
        [--cells a,b]

The step is ``optim.accumulation.make_train_step`` over the cell's model
and AdamW at the cell's size (its chips' data mesh for a four-chip
cell), lowered with ``lowering_platforms=("tpu",)``. A Pallas kernel's
serialized body carries source file names and line numbers; each body
is replaced by the sha256 of its assembly without them, so two
checkouts at different paths, or a kernel file whose lines moved, hash
alike when the programs are the same. A private function's numeric
suffix counts every clash of names while the step was lowered, those of
functions dropped later too: each is renumbered within its name, so a
trace that made one more ``_where`` on the way, and kept the same
program, hashes alike as well. ``--root`` imports the program
and the benchmark from another checkout (a parent's, unpacked with
``git archive``).
"""
import argparse
import base64
import hashlib
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def normalize(text: str) -> str:
    """The lowered text with each Mosaic body as its location-free
    assembly's sha256 and each private function numbered within its
    name."""
    from jax._src.lib.mlir import ir

    def body(m):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(m.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return "body:" + hashlib.sha256(asm.encode()).hexdigest()

    text = re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)
    names, seen = {}, {}
    for sym in re.findall(r"func\.func private @([A-Za-z0-9_]+)", text):
        base = re.sub(r"_[0-9]+$", "", sym)
        seen[base] = seen.get(base, -1) + 1
        names[sym] = f"{base}.{seen[base]}"
    return re.sub(r"@([A-Za-z0-9_]+)\b",
                  lambda m: "@" + names.get(m.group(1), m.group(1)), text)


def lowered_step(cell_name: str, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks import manifest, model_setup
    from bigdl_tpu import optim
    from bigdl_tpu.optim.accumulation import make_train_step
    from bigdl_tpu.parallel.engine import Engine
    cell = manifest.data_file("workloads", cell_name)
    cfg = manifest.data_file("configs", cell["config"])
    traffic = manifest.data_file("traffic", cell["traffic"])
    model_setup.set_dtype_policy(cfg["policy"])
    builder = manifest.plugin("builders", cfg["builder"])
    model = builder.build(cfg)
    chips = int(cell["chips"])
    mesh = Engine.init(axes={"data": chips}, devices=topo.devices[:chips])
    whole, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    def placed(tree, sharding):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    params = placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)), whole)
    state = model.init_state()
    spec = dict(traffic["optimizer"])
    method = getattr(optim, spec.pop("name"))(**spec)
    opt_state = placed(jax.eval_shape(method.init_state, params), whole)
    step = make_train_step(fwd=model.apply, criterion=builder.criterion(),
                           update_fn=method.update)
    batch = int(traffic["batch_per_chip"]) * chips
    ids = jax.ShapeDtypeStruct((batch, int(traffic["seq_len"])), jnp.int32,
                               sharding=rows)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=whole)
    epoch = jax.ShapeDtypeStruct((), jnp.int32, sharding=whole)
    # traced under the mesh, as DistriOptimizer traces the step: that is
    # where a Pallas kernel learns its per-shard split
    with jax.set_mesh(mesh):
        return jax.jit(step, donate_argnums=(0, 1, 2)).trace(
            params, state, opt_state, key, ids, ids, epoch).lower(
            lowering_platforms=("tpu",)).as_text()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--cells", default="")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    from jax.experimental import topologies

    from benchmarks import manifest
    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"       # the program's TPU path
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cells = args.cells.split(",") if args.cells else [
        w["name"] for w in manifest.load_manifest()["workloads"]
        if manifest.data_file("workloads", w["name"])["kind"] == "train"]
    for name in cells:
        text = lowered_step(name, topo)
        print(f"{name}: {hashlib.sha256(normalize(text).encode()).hexdigest()}"
              f" ({len(text)} bytes, "
              f"{text.count('tpu_custom_call')} tpu_custom_call)", flush=True)


if __name__ == "__main__":
    main()
