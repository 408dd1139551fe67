"""How often the bf16 program and the float32 reference ROUTE differently
in the Kimi cell, on the chip, and what the harness's check then sees:

    chiprun -- python3 dev/kimi_flips.py --seeds 3300000101 [--leaves]

For each seed, at the cell's size (configuration, weights and batch as
``benchmarks/run.py`` makes them): the program's forward pass a block at
a time beside the reference's a layer at a time, and per expert layer
the share of tokens whose set of 6 experts differs from the reference's,
the share of the assignments that land on the experts HELD here that
differ (what moves the held experts' gradients), the share of all
assignments landing here, how much of the router input's energy is one
vector common to every token, and how far the hidden state is off. Each
side routes from its OWN hidden state, so a layer's count holds what the
layers before it added. With ``--leaves`` one run of the cell under the
harness's own check follows (``--seconds 5``), printing every leaf's
error as the check computes it. With ``--controls`` the harness's check
(``kinds/train.check``, floor and all) is called on the cell's model at
the cell's size as it is and with each of ``CONTROLS`` planted in the
PROGRAM, to see which of them ``correct`` refuses and by which leaves.
``--embedding-std`` draws the token embedding at another deviation than
``KimiLM``'s (the other cells' is hidden^-1/2: 0.0221). PERF.md section 6
(PR 33) quotes all three. Refuses to run without a TPU; ``--rehearsal``
walks the same code at the data files' tiny widths on any backend and
is never a result.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "kimi-vl-a3b-instruct.train.seq8192"


def flips(args, seed):
    import jax
    import jax.numpy as jnp

    from benchmarks import loadgen, manifest, model_setup
    cell = manifest.data_file("workloads", CELL)
    cfg = manifest.data_file("configs", cell["config"])
    traffic = manifest.data_file("traffic", cell["traffic"])
    if args.rehearsal:
        cfg, traffic = (dict(x, **x["rehearsal"]) for x in (cfg, traffic))
    builder = manifest.plugin("builders", cfg["builder"])
    ref = manifest.plugin("reference", cfg["reference"])
    model_setup.set_dtype_policy(cfg["policy"])
    model = builder.build(cfg)
    heads, n = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    lo, held = cfg["experts_offset"], cfg["n_routed_experts"]

    def sys_layer(p, x, i):
        blk = model.modules[1 + i]
        att_res, ffn_res = blk.modules
        state = blk.init_state()
        h = att_res.apply(p["0"], state["0"], x)[0]
        top = None
        if i >= cfg["first_k_dense_replace"]:
            u2 = ffn_res.modules[0].apply(p["1"]["0"], {}, h)[0]
            top = ffn_res.modules[1].route(p["1"]["1"], u2[0])[0]
        return ffn_res.apply(p["1"], state["1"], h)[0], top

    sys_layer = jax.jit(sys_layer, static_argnums=2)

    def ref_route(lw, x, spec):
        with jax.default_matmul_precision("highest"):
            h = x + ref._attention(lw, ref._rms(x, lw["ln1_g"], spec.eps),
                                   heads, spec)
            u2 = ref._rms(h, lw["ln2_g"], spec.eps)
            common = jnp.sum(jnp.mean(u2, 0) ** 2) / jnp.mean(
                jnp.sum(u2 ** 2, -1))
            return ref.route(lw, u2, spec)[0], common

    ref_route = jax.jit(ref_route, static_argnums=2)
    params = model_setup.init_params(model, seed, jax.devices()[0])
    w = builder.reference_weights(params, cfg)
    data, _ = next(loadgen.train_batches(
        cfg["vocab_size"], 1, int(traffic["seq_len"]), seed))
    x = model.modules[0].apply(params["0"], {}, jnp.asarray(data))[0]
    x_ref = ref._embed_jit(w["tok"], jnp.asarray(data[0] - 1))
    for i in range(n):
        lw = w["layers"][i]
        x_next, top = sys_layer(params[str(1 + i)], x, i)
        said = ""
        if top is not None:
            top_ref, common = ref_route(lw, x_ref, w.spec)
            total = cfg["published"]["n_routed_experts"]
            mine = jnp.any(top[..., None] == jnp.arange(total), 1)
            theirs = jnp.any(top_ref[..., None] == jnp.arange(total), 1)
            here = slice(lo, lo + held)
            differ = jnp.sum(mine[:, here] != theirs[:, here])
            said = (f"tokens with another expert set "
                    f"{float(jnp.mean(jnp.any(mine != theirs, -1))):.4f}, "
                    f"assignments held here that differ "
                    f"{float(differ / jnp.sum(theirs[:, here])):.4f} of "
                    f"{int(jnp.sum(theirs[:, here]))} (share of all "
                    f"{float(jnp.mean(theirs[:, here]) * total / 6):.3f}"
                    f"; busiest held expert "
                    f"{int(jnp.max(jnp.sum(theirs[:, here], 0)))}), "
                    f"common part of the router's input "
                    f"{float(common):.3f}, ")
        x, x_ref = x_next, ref._layer_jit(lw, x_ref, heads, w.spec)
        off = jnp.linalg.norm(x[0] - x_ref) / jnp.linalg.norm(x_ref)
        print(f"seed {seed} layer {i}: {said}hidden state off by "
              f"{float(off):.5f}", flush=True)


def leaves(args, seed):
    import jax
    import jax.numpy as jnp

    from benchmarks import run
    from benchmarks.builders import kimi as builder
    from benchmarks.kinds import train
    from benchmarks.reference import kimi as ref
    seen = {}
    real = (ref.loss_and_grads, builder.reference_weights, train.check)

    def loss_and_grads(*a):
        out = real[0](*a)
        seen["ref"] = out[1]
        return out

    def reference_weights(tree, cfg):
        seen["sys"] = real[1](tree, cfg)      # the check's last: g_sys
        return seen["sys"]

    def check(*a, **k):
        out = real[2](*a, **k)
        flat = jax.tree_util.tree_flatten_with_path(seen["sys"])[0]
        refs = jax.tree.leaves(seen["ref"])
        norms = [float(jnp.linalg.norm(r)) for r in refs]
        floor = train.GRAD_FLOOR * max(norms)
        for (path, g), r, norm in zip(flat, refs, norms):
            err = float(jnp.linalg.norm(g.astype(jnp.float32) - r)) \
                / max(norm, floor)
            print(f"leaves: {jax.tree_util.keystr(path)}: reference norm "
                  f"{norm:.4e} ({norm / floor:.1f} x the floor), error "
                  f"{err:.4f}", flush=True)
        return out

    ref.loss_and_grads, builder.reference_weights, train.check = (
        loss_and_grads, reference_weights, check)
    argv = ["--workload", CELL, "--seed", str(seed), "--seconds", "5",
            "--trace", "0"]
    try:
        return run.main(argv + (["--rehearsal"] if args.rehearsal else []))
    finally:
        ref.loss_and_grads, builder.reference_weights, train.check = real


CONTROLS = ("as_it_is", "rotary_part_dropped", "latent_norm_dropped",
            "softmax_router", "weights_to_3_mantissa_bits",
            "weights_to_2_mantissa_bits")
KINDS = {"attention": ("q_w", "kva_w", "kvb_w", "o_w"),
         "norm weights": ("ln1_g", "ln2_g", "kvn_g", "lnf_g"),
         "router": ("router_w",),
         "shared expert": ("sh_gate_w", "sh_up_w", "sh_down_w"),
         "embedding": ("tok",), "head": ("head_w",)}


def _kind(path: str) -> str:
    for kind, names in KINDS.items():
        if any(f"'{n}'" in path for n in names):
            return kind
    return "dense layer" if "[0]" in path else "routed experts"


def _planted(stack, control, model):
    """Plant ``control`` in the program for as long as ``stack`` lives."""
    import jax
    import jax.numpy as jnp
    import pytest

    from bigdl_tpu.nn.attention import LatentAttention
    from bigdl_tpu.ops.pallas import latent_attention as kernels
    from bigdl_tpu.parallel.expert import ExpertShare
    mp = stack.enter_context(pytest.MonkeyPatch.context())
    if control == "rotary_part_dropped":
        for name in ("latent_attention", "latent_attention_xla"):
            real = getattr(kernels, name)
            mp.setattr(kernels, name,
                       lambda qn, qr, kn, kr, v, _real=real: _real(
                           qn, jnp.zeros_like(qr), kn, kr, v))
    elif control == "latent_norm_dropped":
        mp.setattr(LatentAttention, "_latent_norm", lambda self, c, w: c)
    elif control == "softmax_router":
        for block in model.modules:
            for res in getattr(block, "modules", ()):
                for m in getattr(res, "modules", ()):
                    if isinstance(m, ExpertShare):
                        mp.setattr(m, "scoring", "softmax")
    elif control.startswith("weights_to_"):
        # every matrix rounded to float8's mantissa (3 bits as e4m3, 2 as
        # e5m2, where the policy's bfloat16 has 7) at float32's exponent,
        # in integer arithmetic: a convert there and back is something
        # XLA may drop. The gradient passes straight through
        drop = 23 - int(control.split("_")[2])

        def rounded(x):
            if x.ndim < 2:
                return x
            u = jax.lax.bitcast_convert_type(x, jnp.uint32)
            u = (u + jnp.uint32(1 << (drop - 1))) \
                & jnp.uint32(0xFFFFFFFF ^ ((1 << drop) - 1))
            q = jax.lax.bitcast_convert_type(u, x.dtype)
            return x + jax.lax.stop_gradient(q - x)

        real = model.apply
        mp.setattr(model, "apply",
                   lambda p, *a, **k: real(jax.tree.map(rounded, p), *a, **k),
                   raising=False)
    else:
        assert control == "as_it_is", control


def controls(args, seed):
    import contextlib
    import types

    import jax
    import jax.numpy as jnp

    from benchmarks import loadgen, manifest, model_setup
    from benchmarks.kinds import train
    cell = manifest.data_file("workloads", CELL)
    cfg = manifest.data_file("configs", cell["config"])
    traffic = manifest.data_file("traffic", cell["traffic"])
    if args.rehearsal:
        cfg, traffic = (dict(x, **x["rehearsal"]) for x in (cfg, traffic))
    builder = manifest.plugin("builders", cfg["builder"])
    ref = manifest.plugin("reference", cfg["reference"])
    model_setup.set_dtype_policy(cfg["policy"])
    model = builder.build(cfg)
    batch, seq = int(traffic["batch_per_chip"]), int(traffic["seq_len"])
    ctx = types.SimpleNamespace(config=cfg, devices=jax.devices()[:1],
                                seed=seed)
    data, labels = next(loadgen.train_batches(cfg["vocab_size"], batch, seq,
                                              seed))
    crit = builder.criterion()
    seen, kept = {}, {}
    real = (ref.loss, ref.loss_and_grads, builder.reference_weights)

    def once(name, fn):
        # the reference's side is the same for every control: computed
        # once, and held on the HOST between them
        def wrapped(*a):
            if name not in kept:
                kept[name] = jax.device_get(fn(*a))
            return kept[name]
        return wrapped

    def reference_weights(tree, cfg_):
        seen["sys"] = real[2](tree, cfg_)     # the check's last: g_sys
        return seen["sys"]

    ref.loss, ref.loss_and_grads = (once("loss", real[0]),
                                    once("grads", real[1]))
    builder.reference_weights = reference_weights
    try:
        for control in args.controls.split(","):
            with contextlib.ExitStack() as stack:
                _planted(stack, control, model)
                params = model_setup.init_params(model, seed, ctx.devices[0])
                first = float(jax.jit(lambda p, x, y: crit.apply(
                    model.apply(p, model.init_state(), x, training=True)[0],
                    y))(params, jnp.asarray(data), jnp.asarray(labels)))
                del params
                out = train.check(ctx, builder, model, first,
                                  [{"loss": first}], batch, seq)
            refs = jax.tree.leaves(kept["grads"][1])
            norms = [float(jnp.linalg.norm(r)) for r in refs]
            floor = train.GRAD_FLOOR * max(norms)
            worst = {}
            flat = jax.tree_util.tree_flatten_with_path(seen.pop("sys"))[0]
            for (path, g), r, norm in zip(flat, refs, norms):
                err = float(jnp.linalg.norm(g.astype(jnp.float32) - r)) \
                    / max(norm, floor)
                kind = _kind(jax.tree_util.keystr(path))
                was = worst.get(kind, (0.0, 0, 0.0, 0.0))
                worst[kind] = (max(was[0], err),
                               was[1] + (err > train.TOL_GRAD_REL),
                               min(was[2] or 1e9, norm / floor),
                               max(was[3], norm / floor))
            del flat
            print(f"control {control} seed {seed}: correct "
                  f"{out['ok']} (loss_rel_err {out['loss_rel_err']:.3e} "
                  f"ok {out['loss_ok']}; grad_rel_err "
                  f"{out['grad_rel_err']:.4f} ok {out['grad_ok']}; worst "
                  f"{out['grad_worst_leaves'][0][1]})", flush=True)
            for kind, (err, over, lo, hi) in sorted(worst.items()):
                print(f"control {control}:   {kind}: worst error {err:.4f}"
                      f", {over} leaves over {train.TOL_GRAD_REL}, "
                      f"reference norm {lo:.2f}-{hi:.2f} x the floor",
                      flush=True)
    finally:
        ref.loss, ref.loss_and_grads, builder.reference_weights = real


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="3300000101")
    ap.add_argument("--leaves", action="store_true")
    ap.add_argument("--controls", nargs="?", const=",".join(CONTROLS),
                    default="")
    ap.add_argument("--no-flips", action="store_true")
    ap.add_argument("--embedding-std", type=float)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu" and not args.rehearsal:
        sys.exit("dev/kimi_flips.py: no TPU")
    if args.embedding_std is not None:
        from bigdl_tpu.models.transformer import model as model_mod
        model_mod.KIMI_EMBEDDING_STD = args.embedding_std
    for seed in (int(s) for s in args.seeds.split(",")):
        if not args.no_flips:
            flips(args, seed)
        if args.leaves:
            leaves(args, seed)
        if args.controls:
            controls(args, seed)


if __name__ == "__main__":
    main()
