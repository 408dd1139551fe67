"""How often the bf16 program and the float32 reference CHOOSE
differently in the Keye cell, on the chip:

    chiprun -- python3 dev/keye_flips.py --seeds 3000000001,3000000002

For each seed, at the cell's size (configuration, weights and batch as
``benchmarks/run.py`` makes them): the program's forward pass a block at
a time beside the reference's a layer at a time, and per layer the share
of queries whose selected key set differs from the reference's (and of
selected pairs that differ; beside it the share that the score
product's precision alone flips: the program's own qI, kI and w scored
with float32 "highest" products instead of two bfloat16 halves), and the
share of tokens whose set of 8 experts differs. Each side chooses from
its OWN hidden state, so a layer's count holds what the layers before it
added. PERF.md section 6 (PR 31) quotes it. Refuses to run without a
TPU; ``--rehearsal`` walks the same code at the data files' tiny widths
on any backend (kernels interpreted) and is never a result.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="3000000001")
    ap.add_argument("--cell", default="keye-vl-2.0-30b-a3b.train.seq16384")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu" and not args.rehearsal:
        sys.exit("dev/keye_flips.py: no TPU")
    from benchmarks import loadgen, manifest, model_setup
    from bigdl_tpu.ops.pallas import sparse_attention as sa
    from bigdl_tpu.tensor import compute_dtype

    cell = manifest.data_file("workloads", args.cell)
    cfg = manifest.data_file("configs", cell["config"])
    traffic = manifest.data_file("traffic", cell["traffic"])
    if args.rehearsal:
        cfg, traffic = (dict(x, **x["rehearsal"]) for x in (cfg, traffic))
    builder = manifest.plugin("builders", cfg["builder"])
    ref = manifest.plugin("reference", cfg["reference"])
    model_setup.set_dtype_policy(cfg["policy"])
    model = builder.build(cfg)
    heads, n = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    seq = int(traffic["seq_len"])

    def sys_layer(p, x, i):
        """(next hidden state, selection (S, S) bool, experts (S, 8),
        share of selected pairs the score product's precision flips)."""
        blk = model.modules[1 + i]
        att_res, moe_res = blk.modules
        u = att_res.modules[0].apply(p["0"]["0"], {}, x)[0]
        att = att_res.modules[1]
        qi, ki, wi = att.indexer(p["0"]["1"], u.astype(compute_dtype()))
        kept = sa.select_rows(sa.index_scores(
            qi.transpose(0, 2, 1, 3), ki, wi, interpret=args.rehearsal),
            att.topk, interpret=args.rehearsal)[0][0] > -jnp.inf
        # the program's OWN qI, kI, w scored once more with float32
        # products ("highest": six MXU passes) and selected by the
        # reference's sort: what the two-half product alone flips
        with jax.default_matmul_precision("highest"):
            six = ref._in_blocks(lambda xs: ref.select(
                ref.index_scores(xs[1], ki[0], xs[2]), xs[0], att.topk),
                (jnp.arange(x.shape[1]), qi[0], wi[0]), ref.QUERY_BLOCK)
        product = jnp.sum(kept != six) / 2 / jnp.sum(six)
        state = blk.init_state()
        x = att_res.apply(p["0"], state["0"], x)[0]
        u2 = moe_res.modules[0].apply(p["1"]["0"], {}, x)[0]
        top = moe_res.modules[1].route(p["1"]["1"], u2[0])[0]
        return moe_res.apply(p["1"], state["1"], x)[0], kept, top, product

    sys_layer = jax.jit(sys_layer, static_argnums=2)

    def ref_choices(lw, x, spec):
        with jax.default_matmul_precision("highest"):
            u = ref._rms(x, lw["ln1_g"], spec.eps)
            kept = ref.selection(lw, u, spec)
            h = x + ref._attention(lw, u, heads, spec)[0]
            top = ref.route(lw, ref._rms(h, lw["ln2_g"], spec.eps), spec)[0]
        return kept, top

    ref_choices = jax.jit(ref_choices, static_argnums=2)

    for seed in (int(s) for s in args.seeds.split(",")):
        params = model_setup.init_params(model, seed, jax.devices()[0])
        w = builder.reference_weights(params, cfg)
        data, _ = next(loadgen.train_batches(cfg["vocab_size"], 1, seq,
                                             seed))
        x = model.modules[0].apply(params["0"], {}, jnp.asarray(data))[0]
        x_ref = ref._embed_jit(w["tok"], jnp.asarray(data[0] - 1))
        for i in range(n):
            lw = w["layers"][i]
            x, kept, top, product = sys_layer(params[str(1 + i)], x, i)
            kept_ref, top_ref = ref_choices(lw, x_ref, w.spec)
            x_ref = ref._layer_jit(lw, x_ref, heads, w.spec)[0]
            differ = kept != kept_ref
            experts = jnp.any(jnp.sort(top, -1) != jnp.sort(top_ref, -1), -1)
            off = jnp.linalg.norm(x[0] - x_ref) / jnp.linalg.norm(x_ref)
            print(f"seed {seed} layer {i}: queries with another key set "
                  f"{float(jnp.mean(jnp.any(differ, -1))):.4f}, selected "
                  f"pairs that differ "
                  f"{float(jnp.sum(differ) / 2 / jnp.sum(kept_ref)):.6f} "
                  f"(by the two-half score product alone "
                  f"{float(product):.6f}), "
                  f"tokens with another expert set "
                  f"{float(jnp.mean(experts)):.4f}, hidden state off by "
                  f"{float(off):.5f}", flush=True)


if __name__ == "__main__":
    main()
