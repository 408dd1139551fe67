"""The SmallThinker cell's check, on a machine with a TPU:

    python3 dev/smallthinker_check.py --seeds 4000000101
    python3 dev/smallthinker_check.py --controls as_it_is,window_dropped

Calls the harness's check (``kinds/train.check``, floor and all) on the
cell's model at the published widths and the cell's 1 x 16384 tokens —
the timed path's first-step loss and every gradient leaf against the
float32 reference (``benchmarks/reference/smallthinker.py``, attention in
query blocks) — and compares the program's logits with the reference's
(relative L2 over all 16384 x 18992). It does so with the program as it
is and with each of ``CONTROLS`` planted in the PROGRAM: the window
dropped from the window layers (they run the causal kernels), RoPE
applied in the global layers, the router fed the experts' input h
instead of the attention's input r, and every matrix rounded to 3
mantissa bits (float8 e4m3's, where the policy's bf16 has 7). Each
control must read correct False. For each: the loss's error, the worst
leaves, every leaf's error as the check reads it (over max(its norm, the
floor)) and over its own norm, and the logits' error. Beside the
harness's verdict it gives its own, tighter one: every leaf within the
harness's TOL_GRAD_REL over its OWN norm, with no floor (the floor, 1% of
the head's norm, stands above the router's and the norms' leaves; at the
cell's size the router fed h passes the harness's check and fails this
one: PERF.md section 6). The reference's side is computed once a seed
and held on the host. Refuses to run
without a TPU; ``--rehearsal`` walks the same code at the data files'
tiny widths on any backend and is never a result.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "smallthinker-21ba3b-instruct.train.seq16384"
CONTROLS = ("as_it_is", "window_dropped", "rope_in_global", "router_fed_h",
            "weights_to_3_mantissa_bits")


def _cell(rehearsal):
    from benchmarks import manifest
    cell = manifest.data_file("workloads", CELL)
    cfg = manifest.data_file("configs", cell["config"])
    traffic = manifest.data_file("traffic", cell["traffic"])
    if rehearsal:
        cfg, traffic = (dict(x, **x["rehearsal"]) for x in (cfg, traffic))
    return cfg, traffic


def _to_mantissa(x, bits):
    """A float32 array rounded to ``bits`` mantissa bits at float32's
    exponent, in integer arithmetic; the gradient passes straight
    through."""
    import jax
    import jax.numpy as jnp
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = (u + jnp.uint32(1 << (drop - 1))) \
        & jnp.uint32(0xFFFFFFFF ^ ((1 << drop) - 1))
    q = jax.lax.bitcast_convert_type(u, jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


def _planted(stack, control, model):
    """Plant ``control`` in the program for as long as ``stack`` lives."""
    import jax
    import pytest

    from bigdl_tpu.parallel.expert import ExpertShare
    mp = stack.enter_context(pytest.MonkeyPatch.context())
    attention = [block.modules[1] for block in model.modules[1:-2]]
    if control == "window_dropped":
        for att in attention:
            mp.setattr(att, "window", None)
    elif control == "rope_in_global":
        for att in attention:
            mp.setattr(att, "rope", True)
    elif control == "router_fed_h":
        real = ExpertShare.apply
        mp.setattr(ExpertShare, "apply", lambda self, *a, route_from=None,
                   **k: real(self, *a, **k))
    elif control.startswith("weights_to_"):
        bits = int(control.split("_")[2])
        real = model.apply
        mp.setattr(model, "apply", lambda p, *a, **k: real(
            jax.tree.map(lambda w: _to_mantissa(w, bits) if w.ndim >= 2
                         else w, p), *a, **k), raising=False)
    else:
        assert control == "as_it_is", control


def check(args, seed):
    import contextlib
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import loadgen, manifest, model_setup
    from benchmarks.kinds import train
    cfg, traffic = _cell(args.rehearsal)
    builder = manifest.plugin("builders", cfg["builder"])
    ref = manifest.plugin("reference", cfg["reference"])
    model_setup.set_dtype_policy(cfg["policy"])
    model = builder.build(cfg)
    heads = cfg["num_attention_heads"]
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
                t = time.perf_counter()
                kept[name] = jax.device_get(fn(*a))
                print(f"check: reference {name} in "
                      f"{time.perf_counter() - t:.1f} s", flush=True)
            return kept[name]
        return wrapped

    def reference_weights(tree, cfg_):
        seen["sys"] = real[2](tree, cfg_)     # the check's last: g_sys
        return seen["sys"]

    ref.loss, ref.loss_and_grads = (once("loss", real[0]),
                                    once("grads", real[1]))
    builder.reference_weights = reference_weights
    ids = jnp.asarray(data[:1] - 1)[0]
    try:
        for control in args.controls.split(","):
            with contextlib.ExitStack() as stack:
                _planted(stack, control, model)
                params = model_setup.init_params(model, seed, ctx.devices[0])
                state = model.init_state()
                # the first-step loss and the first sequence's logits
                # from ONE program (batch 1: the cell's)
                first, logits = jax.jit(lambda p, x, y: (lambda o: (
                    crit.apply(o, y), o[0]))(model.apply(
                        p, state, x, training=True)[0]))(
                    params, jnp.asarray(data), jnp.asarray(labels))
                first, logits = float(first), np.asarray(logits)
                if "logits" not in kept:      # on the host, as the grads
                    kept["logits"] = np.asarray(ref.logits(
                        real[2](params, cfg), ids, heads))
                logit_err = float(np.linalg.norm(logits - kept["logits"])
                                  / np.linalg.norm(kept["logits"]))
                del params, logits
                out = train.check(ctx, builder, model, first,
                                  [{"loss": first}], batch, seq)
            refs = jax.tree.leaves(kept["grads"][1])
            norms = [float(jnp.linalg.norm(r)) for r in refs]
            floor = train.GRAD_FLOOR * max(norms)
            flat = jax.tree_util.tree_flatten_with_path(seen.pop("sys"))[0]
            own = []
            for (path, g), r, norm in zip(flat, refs, norms):
                diff = float(jnp.linalg.norm(g.astype(jnp.float32) - r))
                own.append((diff / norm, jax.tree_util.keystr(path)))
                print(f"leaves {control}: {own[-1][1]}: "
                      f"reference norm {norm:.4e} ({norm / floor:.2f} x the "
                      f"floor), error {diff / max(norm, floor):.4f}, over "
                      f"its own norm {diff / norm:.4f}", flush=True)
            del flat
            # this script's own, tighter reading: EVERY leaf over its own
            # norm, no floor (the harness's floor, 1% of the head's norm,
            # stands above the router's and the norms' leaves)
            worst_own = max(own)
            print(f"control {control} seed {seed}: correct {out['ok']} "
                  f"(loss_rel_err {out['loss_rel_err']:.3e} ok "
                  f"{out['loss_ok']}; grad_rel_err "
                  f"{out['grad_rel_err']:.4f} ok {out['grad_ok']}; worst "
                  f"{[(round(e, 4), w) for e, w in out['grad_worst_leaves']]}"
                  f"; logits_rel_err {logit_err:.4e}); over its own norm "
                  f"worst {worst_own[0]:.4f} {worst_own[1]} ok "
                  f"{worst_own[0] <= train.TOL_GRAD_REL}", flush=True)
    finally:
        ref.loss, ref.loss_and_grads, builder.reference_weights = real


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="4000000101")
    ap.add_argument("--controls", default=",".join(CONTROLS))
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu" and not args.rehearsal:
        sys.exit("dev/smallthinker_check.py: no TPU")
    for seed in (int(s) for s in args.seeds.split(",")):
        check(args, seed)


if __name__ == "__main__":
    main()
