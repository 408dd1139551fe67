"""The Nemotron cell's scan and its check, on the chip:

    chiprun -- python3 dev/nemotron_check.py --sweep
    chiprun -- python3 dev/nemotron_check.py --controls --seeds 3700000101

``--sweep`` times ONE Mamba layer's scan at the cell's shape (2 x 8192
tokens, 64 heads of 64, 8 groups of a 128-wide state, chunks of 128),
forward and forward + backward, on the Pallas kernels
(``ops/pallas/ssd.py``) and on ``ssd_chunked_xla``, in one process, and
says how far the two agree. ``--controls`` calls the harness's check
(``kinds/train.check``, floor and all) on the cell's model at the cell's
size as it is and with each of ``CONTROLS`` planted in the PROGRAM,
printing for each the loss's error, the worst leaves and every gradient
leaf's error as the check reads it (over max(its norm, the floor)) and
over its own norm, with that norm over the check's floor. Three of the
controls lower a precision the configuration states: the scan's carried
state rounded to bf16 every chunk, the states the backward reads
rounded to bf16, and every matrix rounded to float8's mantissa (3 bits
as e4m3, 2 as e5m2, where the policy's bf16 has 7). The conv one step
ahead and the gate after the norm are planted where the mixer runs them:
in ``causal_conv1d`` / ``gated_rms_norm`` on the CPU, around the fused
passes of ``ops/pallas/ssm_pointwise.py`` on the TPU. PERF.md section 6
quotes them. Refuses to run
without a TPU; ``--rehearsal`` walks the same code at the data files'
tiny widths on any backend and is never a result.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "nemotron-3-nano-30b-a3b.train.seq8192"
CONTROLS = ("as_it_is", "state_carried_in_bf16", "states_saved_in_bf16",
            "weights_to_3_mantissa_bits", "weights_to_2_mantissa_bits",
            "D_dropped", "conv_one_step_ahead", "gate_after_the_norm")


def _cell(rehearsal):
    from benchmarks import manifest
    cell = manifest.data_file("workloads", CELL)
    cfg = manifest.data_file("configs", cell["config"])
    traffic = manifest.data_file("traffic", cell["traffic"])
    if rehearsal:
        cfg, traffic = (dict(x, **x["rehearsal"]) for x in (cfg, traffic))
    return cfg, traffic


def sweep(args):
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.nn.state_space import ssd_chunked_xla
    from bigdl_tpu.ops.pallas import ssd as kernels
    cfg, traffic = _cell(args.rehearsal)
    b, s = int(traffic["batch_per_chip"]), int(traffic["seq_len"])
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, chunk = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    bf16, f32 = jnp.bfloat16, jnp.float32
    args_ = (jax.random.normal(k[0], (b, s, h, p), bf16),
             jax.nn.softplus(jax.random.normal(k[1], (b, s, h), f32) - 3.0),
             -jnp.exp(jax.random.uniform(k[2], (h,), f32, 0.0, 2.77)),
             (0.3 * jax.random.normal(k[3], (b, s, g, n))).astype(bf16),
             (0.3 * jax.random.normal(k[4], (b, s, g, n))).astype(bf16),
             jnp.ones((h,), f32))
    dy = jax.random.normal(k[5], (b, s, h, p), bf16)
    interpret = args.rehearsal
    paths = {
        "kernel": lambda *a: kernels.ssd(*a, chunk=chunk,
                                         interpret=interpret),
        "chunked_xla": lambda *a: ssd_chunked_xla(*a, chunk=chunk)}
    got = {}
    for name, fn in paths.items():
        fwd = jax.jit(fn)
        both = jax.jit(lambda *a, _fn=fn: jax.vjp(_fn, *a)[1](dy))
        for what, prog in (("forward", fwd), ("forward+backward", both)):
            out = jax.block_until_ready(prog(*args_))
            times = []
            for _ in range(args.repeats):
                t = time.perf_counter()
                jax.block_until_ready(prog(*args_))
                times.append(time.perf_counter() - t)
            times.sort()
            print(f"sweep: {name} {what}: median "
                  f"{1e3 * times[len(times) // 2]:.3f} ms, best "
                  f"{1e3 * times[0]:.3f} ms over {len(times)}", flush=True)
            got[(name, what)] = out
    y_k, y_x = got[("kernel", "forward")], got[("chunked_xla", "forward")]
    rel = float(jnp.linalg.norm((y_k - y_x).astype(f32))
                / jnp.linalg.norm(y_x.astype(f32)))
    print(f"sweep: kernel vs chunked_xla forward, relative L2 {rel:.3e}",
          flush=True)
    for i, name in enumerate(("x", "Delta", "A", "B", "C", "D")):
        a = got[("kernel", "forward+backward")][i].astype(f32)
        c = got[("chunked_xla", "forward+backward")][i].astype(f32)
        print(f"sweep: d{name} relative L2 "
              f"{float(jnp.linalg.norm(a - c) / jnp.linalg.norm(c)):.3e}",
              flush=True)


def _to_mantissa(x, bits):
    """A float32 array rounded to ``bits`` mantissa bits at float32's
    exponent, in integer arithmetic (a convert to a narrower float and
    back is something XLA may drop); the gradient passes straight
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
    import jax.numpy as jnp
    import pytest

    from bigdl_tpu.nn import state_space
    from bigdl_tpu.ops.pallas import ssd as kernels
    from bigdl_tpu.ops.pallas import ssm_pointwise as pointwise
    mp = stack.enter_context(pytest.MonkeyPatch.context())
    if control == "state_carried_in_bf16":
        real = kernels._fwd_kernel

        def rounded(*refs, **kw):
            real(*refs, **kw)
            scr = refs[-1]
            scr[...] = scr[...].astype(jnp.bfloat16).astype(jnp.float32)
        mp.setattr(kernels, "_fwd_kernel", rounded)
        kernels._fwd_call.cache_clear()
        stack.callback(kernels._fwd_call.cache_clear)
    elif control == "states_saved_in_bf16":
        real = kernels._fwd_call

        def saved(*a):
            call = real(*a)

            def run(*x):
                y, states = call(*x)
                return y, _to_mantissa(states, 7)
            return run
        mp.setattr(kernels, "_fwd_call", saved)
    elif control.startswith("weights_to_"):
        bits = int(control.split("_")[2])
        real = model.apply
        mp.setattr(model, "apply", lambda p, *a, **k: real(
            jax.tree.map(lambda w: _to_mantissa(w, bits) if w.ndim >= 2
                         else w, p), *a, **k), raising=False)
    elif control == "D_dropped":
        for name in ("ssd", "ssd_chunked_xla"):
            mod = kernels if name == "ssd" else state_space
            real = getattr(mod, name)
            mp.setattr(mod, name, lambda x, dt, a, bm, cm, d, _r=real, **k:
                       _r(x, dt, a, bm, cm, d * 0, **k))
    elif control == "conv_one_step_ahead":
        # the CPU's path: the conv's window one position later
        def ahead(x, w, b):
            k = w.shape[1]
            xs = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 2, 1), (0, 0)))
            s = x.shape[1]
            w = w.astype(jnp.float32)
            return b.astype(jnp.float32) + sum(xs[:, i:i + s] * w[:, i]
                                               for i in range(k))
        mp.setattr(state_space, "causal_conv1d", ahead)
        # the TPU's: the fused pass reads its input one position later
        # (x[t + 1] where x[t] was; the first input is lost to the three
        # first outputs)
        real = pointwise.conv_silu_split
        mp.setattr(pointwise, "conv_silu_split",
                   lambda x, w, b, _r=real, **k: _r(
                       jnp.pad(x[:, 1:], ((0, 0), (0, 1), (0, 0))), w, b,
                       **k))
    elif control == "gate_after_the_norm":
        def after(y, z, w, groups, eps):
            y = y.astype(jnp.float32)
            shape = y.shape
            g = y.reshape(*shape[:-1], groups, shape[-1] // groups)
            g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                                  + eps)
            return g.reshape(shape) * w.astype(jnp.float32) \
                * jax.nn.silu(z.astype(jnp.float32))
        mp.setattr(state_space, "gated_rms_norm", after)
        # the TPU's: the fused pass gated by a constant (silu(10), which
        # the norm divides out, eps / 100 aside), the gate applied after
        real = pointwise.gated_norm
        mp.setattr(pointwise, "gated_norm",
                   lambda y, z, w, groups, eps, _r=real, **k: (_r(
                       y, jnp.full_like(z, 10.0), w, groups, eps, **k)
                       * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype))
    else:
        assert control == "as_it_is", control


def controls(args, seed):
    import contextlib
    import types

    import jax
    import jax.numpy as jnp

    from benchmarks import loadgen, manifest, model_setup
    from benchmarks.kinds import train
    cfg, traffic = _cell(args.rehearsal)
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
                t = time.perf_counter()
                kept[name] = jax.device_get(fn(*a))
                print(f"controls: reference {name} in "
                      f"{time.perf_counter() - t:.1f} s", flush=True)
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
            flat = jax.tree_util.tree_flatten_with_path(seen.pop("sys"))[0]
            print(f"control {control} seed {seed}: correct {out['ok']} "
                  f"(loss_rel_err {out['loss_rel_err']:.3e} ok "
                  f"{out['loss_ok']}; grad_rel_err "
                  f"{out['grad_rel_err']:.4f} ok {out['grad_ok']}; worst "
                  f"{[(round(e, 4), w) for e, w in out['grad_worst_leaves']]}"
                  f")", flush=True)
            for (path, g), r, norm in zip(flat, refs, norms):
                diff = float(jnp.linalg.norm(g.astype(jnp.float32) - r))
                print(f"leaves {control}: {jax.tree_util.keystr(path)}: "
                      f"reference norm {norm:.4e} ({norm / floor:.2f} x the "
                      f"floor), error {diff / max(norm, floor):.4f}, over "
                      f"its own norm {diff / norm:.4f}", flush=True)
            del flat
    finally:
        ref.loss, ref.loss_and_grads, builder.reference_weights = real


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--seeds", default="3700000101")
    ap.add_argument("--controls", nargs="?", const=",".join(CONTROLS),
                    default="")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu" and not args.rehearsal:
        sys.exit("dev/nemotron_check.py: no TPU")
    if args.sweep:
        sweep(args)
    for seed in (int(s) for s in args.seeds.split(",") if args.controls):
        controls(args, seed)


if __name__ == "__main__":
    main()
