"""How the Keye cell's step time follows its routing, on the chip:

    chiprun -- python3 dev/keye_routing.py --seeds 3000000001,3000000002

1. ``parallel.expert.ExpertShare`` alone at the cell's shape (16384
   tokens of 2048, 16 of 128 experts of 768 held, 8 a token), forward
   and backward, with the router biased so that a growing share of the
   assignments lands here: milliseconds a call beside the share, the
   busiest expert's load and how many chunks of sorted rows ran.
2. For each seed the cell's own training step (``make_train_step`` on
   the builder's model, AdamW as the traffic file says, weights and
   batches as ``benchmarks/run.py`` makes them), ``--steps`` steps one
   at a time: milliseconds a step beside each layer's
   ``moe_local_assignment_share`` and ``moe_held_load_max`` read from
   the module state the step returns.

PERF.md section 6 (PR 31) quotes it. Refuses to run without a TPU;
``--rehearsal`` walks the same code at the data files' tiny widths on
any backend and is never a result.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ms(fn, *args, iters=5):
    import jax
    jax.block_until_ready(fn(*args))          # compiles
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / iters


def layer_alone(cfg, tokens):
    import jax
    import jax.numpy as jnp
    from bigdl_tpu.parallel import expert
    held, total = cfg["num_experts"], cfg["published"]["num_experts"]
    k, off = cfg["num_experts_per_tok"], cfg["experts_offset"]
    layer = expert.ExpertShare(cfg["hidden_size"],
                               cfg["moe_intermediate_size"], total, k,
                               experts_held=held, experts_offset=off)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (1, tokens, cfg["hidden_size"]), jnp.float32)
    x = x.at[..., 0].set(4.0)                 # the router's bias rides it
    rows = expert._chunk_rows(tokens * k, held, total,
                              layer.bias_update_rate is not None)

    def loss(p, x):
        y, state = layer.apply(p, layer.init_state(), x, training=True)
        return jnp.sum(y.astype(jnp.float32) ** 2), state

    both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))
    fwd = jax.jit(lambda p, x: layer.apply(p, layer.init_state(), x)[0])
    print(f"ExpertShare alone: {tokens} tokens, {held} of {total} experts, "
          f"{k} a token, a first span of {rows} sorted rows", flush=True)
    for bias in (-0.5, 0.0, 0.1, 0.2, 0.3, 0.5, 1.0):
        p = dict(params, router_weight=params["router_weight"].at[
            off:off + held, 0].set(bias))
        state = both(p, x)[0][1]
        share = float(state["moe_local_assignment_share"])
        print(f"  bias {bias:5.2f}: share here {share:.4f} "
              f"({share * tokens * k / rows:.2f} first spans), busiest expert "
              f"{float(state['moe_held_load_max']):.0f} rows, forward "
              f"{_ms(fwd, p, x):7.2f} ms, forward+backward "
              f"{_ms(both, p, x):7.2f} ms", flush=True)


def training(cfg, traffic, builder, seeds, steps):
    import jax
    import jax.numpy as jnp
    from benchmarks import loadgen, model_setup
    from bigdl_tpu import optim
    from bigdl_tpu.optim.accumulation import make_train_step
    from bigdl_tpu.parallel.expert import moe_state_stats
    model = builder.build(cfg)
    spec = dict(traffic["optimizer"])
    method = getattr(optim, spec.pop("name"))(**spec)
    step = jax.jit(make_train_step(fwd=model.apply,
                                   criterion=builder.criterion(),
                                   update_fn=method.update),
                   donate_argnums=(0, 1, 2))
    dev = jax.devices()[0]
    seq, batch = int(traffic["seq_len"]), int(traffic["batch_per_chip"])
    for seed in seeds:
        params = model_setup.init_params(model, seed, dev)
        state, opt_state = model.init_state(), method.init_state(params)
        key = jax.random.PRNGKey(0)
        batches = loadgen.train_batches(cfg["vocab_size"], batch, seq, seed)
        for i in range(steps):
            data, labels = next(batches)
            t0 = time.perf_counter()
            params, state, opt_state, loss = step(
                params, state, opt_state, key, jnp.asarray(data),
                jnp.asarray(labels), jnp.int32(0))
            loss = float(loss)
            ms = 1e3 * (time.perf_counter() - t0)
            stats = jax.device_get(moe_state_stats(state))
            layers = sorted(stats, key=lambda p: int(p.split("/")[0]))
            share = [float(stats[p]["moe_local_assignment_share"])
                     for p in layers]
            busiest = [int(stats[p]["moe_held_load_max"]) for p in layers]
            print(f"seed {seed} step {i:2d}: {ms:8.1f} ms, loss {loss:.4f}, "
                  f"share here a layer "
                  f"{' '.join(f'{s:.4f}' for s in share)} (sum "
                  f"{sum(share):.4f}), busiest expert's rows {busiest}",
                  flush=True)
        del params, state, opt_state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="3000000001,3000000002")
    ap.add_argument("--steps", type=int, default=36)
    ap.add_argument("--cell", default="keye-vl-2.0-30b-a3b.train.seq16384")
    ap.add_argument("--only", default="layer,training")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu" and not args.rehearsal:
        sys.exit("dev/keye_routing.py: no TPU")
    from benchmarks import manifest, model_setup

    cell = manifest.data_file("workloads", args.cell)
    cfg = manifest.data_file("configs", cell["config"])
    traffic = manifest.data_file("traffic", cell["traffic"])
    if args.rehearsal:
        cfg, traffic = (dict(x, **x["rehearsal"]) for x in (cfg, traffic))
    builder = manifest.plugin("builders", cfg["builder"])
    model_setup.set_dtype_policy(cfg["policy"])
    if "layer" in args.only:
        layer_alone(cfg, int(traffic["seq_len"])
                    * int(traffic["batch_per_chip"]))
    if "training" in args.only:
        training(cfg, traffic, builder,
                 [int(s) for s in args.seeds.split(",")], args.steps)


if __name__ == "__main__":
    main()
