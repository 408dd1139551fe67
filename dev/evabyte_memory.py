#!/usr/bin/env python3
"""Compile-only memory analysis of a one-chip training cell for a
described v5e (benchmarks/README.md, rehearsal ladder step 2; no chip,
no chip time) — the EvaByte cell, or with ``--cell`` another whose
reference has ``_layer_vjp`` / ``_head_vjp`` (the last layer's is the
one compiled: kimi's first is its dense one; keye-vl-2.0-30b-a3b.train.
seq16384):

    JAX_PLATFORMS=cpu python3 dev/evabyte_memory.py [--cell NAME] \
        [--layers N] [--seq 16384] [--batch 1]

Three programs at the configuration's widths: the training step
``make_train_step`` builds (AdamW, donated state), the check's bare
``jax.grad(model.apply + criterion)`` on one sequence, and the
largest programs of the float32 reference's ``loss_and_grads`` on one
sequence. The program picks its
kernels by ``jax.default_backend()``: steered to "tpu" HERE, not by an
option of the program. A compile that passes is not a chip run.
"""
import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="evabyte-6.5b.train.long")
    ap.add_argument("--layers", type=int, help="default: the file's")
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--only", default="step,check,reference")
    ap.add_argument("--text", help="write the compiled step's text here")
    args = ap.parse_args()

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import manifest, model_setup
    from bigdl_tpu import optim
    from bigdl_tpu.observability.tracing import matmuls_fed_by
    from bigdl_tpu.optim.accumulation import make_train_step

    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    cell = manifest.data_file("workloads", args.cell)
    cfg = manifest.data_file("configs", cell["config"])
    if args.layers:
        cfg = dict(cfg, num_hidden_layers=args.layers)
    traffic = manifest.data_file("traffic", cell["traffic"])
    builder = manifest.plugin("builders", cfg["builder"])
    reference = manifest.plugin("reference", cfg["reference"])
    model_setup.set_dtype_policy(cfg["policy"])
    model = builder.build(cfg)
    crit = builder.criterion()

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    state = model.init_state()
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"{cfg['num_hidden_layers']} layers, {n / 1e6:.1f}M parameters, "
          f"{args.batch} x {args.seq} tokens")
    ids = jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32,
                               sharding=chip)

    def report(name, lowered):
        t = time.time()
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        held = m.argument_size_in_bytes + m.output_size_in_bytes \
            - m.alias_size_in_bytes
        print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.2f} GB, "
              f"outputs {m.output_size_in_bytes / 1e9:.2f}, aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f}, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.2f}; arguments + outputs - "
              f"aliased + temporaries "
              f"{(held + m.temp_size_in_bytes) / 1e9:.2f}"
              f" GB; code {m.generated_code_size_in_bytes / 1e6:.1f} MB "
              f"(compiled in {time.time() - t:.0f} s)", flush=True)
        return compiled

    only = args.only.split(",")
    if "step" in only:
        spec = dict(traffic["optimizer"])
        method = getattr(optim, spec.pop("name"))(**spec)
        opt_state = on_chip(jax.eval_shape(method.init_state, params))
        step = make_train_step(fwd=model.apply, criterion=crit,
                               update_fn=method.update)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
        epoch = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        text = report("step", jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            params, state, opt_state, key, ids, ids, epoch)).as_text()
        if args.text:
            with open(args.text, "w") as f:
                f.write(text)
        # a producer fused into a matmul's operand is evaluated again on
        # every pass over it (PERF.md section 6, PR 28)
        kernels = text.count('custom_call_target="tpu_custom_call"')
        print(f"step: {kernels} Pallas custom calls, "
              f"{text.count(' conditional(')} conditionals", flush=True)
        fed = matmuls_fed_by(text, "exponential")
        backward = sum("transpose(" in scope for scope in fed.values())
        print(f"step: {len(fed)} matmul fusions read an exponential "
              f"through a fused producer, {backward} of them in the "
              f"backward pass", flush=True)
    one = jax.ShapeDtypeStruct((1, args.seq), jnp.int32, sharding=chip)
    if "check" in only:
        def sys_loss(p, x, y):
            logits, _ = model.apply(p, state, x, training=True)
            return crit.apply(logits, y)
        report("check (system)", jax.jit(jax.grad(sys_loss)).lower(
            params, one, one))
    if "reference" in only:
        # the reference's largest programs: one layer's and the head's
        # gradients (it runs one at a time and fetches each to the host)
        w = builder.reference_weights(params, cfg)
        x = jax.ShapeDtypeStruct((args.seq, cfg["hidden_size"]),
                                 jnp.float32, sharding=chip)
        report("check (reference, a layer's vjp)",
               reference._layer_vjp.lower(w["layers"][-1], x, x,
                                          cfg["num_attention_heads"],
                                          w.spec))
        report("check (reference, the head's vjp)",
               reference._head_vjp.lower(
                   reference._head_of(w), x,
                   jax.ShapeDtypeStruct((args.seq,), jnp.int32,
                                        sharding=chip), w.spec))


if __name__ == "__main__":
    main()
