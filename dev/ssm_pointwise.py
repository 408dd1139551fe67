"""One Mamba-2 layer's pointwise passes on the chip, fused and not:

    python3 dev/ssm_pointwise.py [--parent FILE] [--tiles]
                                 [--profile DIR]
                                 [--only passes|layer]    # one TPU chip

At the Nemotron cell's shape (2 x 8192 tokens, hidden 2688, 64 heads of
64, 8 groups of a 128-wide state, a conv of 4, bf16 compute) it times,
median of ``--repeats`` in one process:

- each pass alone, forward and forward + backward: the Pallas passes of
  ``ops/pallas/ssm_pointwise.py`` and their ``jax.numpy`` forms
  (``causal_conv1d`` + silu + the split, ``gated_rms_norm``; the scan's
  layouts on the one side, the mixer's on the other), with the bytes each
  moves at its bound (every tensor read or written once) over its time,
  as a share of the v5e's 819 GB/s (a fused pass alone also pays the
  copy its program makes to return x in another layout: the kernels'
  own times are ``--profile``'s);
- the whole ``nn.Mamba2Mixer`` forward and forward + backward on the
  TPU's path as it is ("fused"), with the two passes swapped for their
  ``jax.numpy`` forms ("unfused"), and ``--parent``'s ``state_space.py``
  (another copy of the module, the parent commit's) beside them;
- ``--tiles``: each pass at other token tiles, column chunks and row
  tiles;
- ``--profile DIR``: a profiler trace of three fused and three unfused
  layer steps, and the 25 operations with the most device time in each.

Outputs and gradients of each fused pass are compared with the
``jax.numpy`` form's. Refuses to run without a TPU; ``--rehearsal``
walks the same code at the data files' tiny widths on the CPU (kernels
interpreted) and is never a result.
"""
from __future__ import annotations

import argparse
import functools
import importlib.util
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "nemotron-3-nano-30b-a3b"
WORKLOAD = "nemotron-3-nano-30b-a3b.train.seq8192"
HBM_BYTES_PER_S = 819e9          # TPU v5e (benchmarks/peaks.py)


def _cell(rehearsal):
    from benchmarks import manifest
    cfg = manifest.data_file("configs", CONFIG)
    traffic = manifest.data_file(
        "traffic", manifest.data_file("workloads", WORKLOAD)["traffic"])
    if rehearsal:
        cfg, traffic = (dict(x, **x["rehearsal"]) for x in (cfg, traffic))
    return cfg, traffic


def _median_ms(fn, args, repeats):
    import jax
    jax.block_until_ready(fn(*args))             # compiles
    times = []
    for _ in range(repeats):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t)
    return 1e3 * statistics.median(times)


def _report(what, ms, nbytes=None):
    line = f"{what}: median {ms:.3f} ms"
    if nbytes:
        bound = 1e3 * nbytes / HBM_BYTES_PER_S
        line += (f", {nbytes / 1e6:.1f} MB at the bound = {bound:.3f} ms, "
                 f"{100 * bound / ms:.1f}% of it")
    print(line, flush=True)


def _rel(a, b):
    import jax.numpy as jnp
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _xla_conv(xbc, w, b, heads, head_dim, groups, state):
    import jax
    from bigdl_tpu.nn.state_space import causal_conv1d
    a = jax.nn.silu(causal_conv1d(xbc, w, b)).astype(xbc.dtype)
    lead, inner, gn = xbc.shape[:2], heads * head_dim, groups * state
    return (a[..., :inner].reshape(*lead, heads, head_dim),
            a[..., inner:inner + gn].reshape(*lead, groups, state),
            a[..., inner + gn:].reshape(*lead, groups, state))


def _xla_norm(y, z, w, groups, eps):
    from bigdl_tpu.nn.state_space import gated_rms_norm
    return gated_rms_norm(y.reshape(z.shape), z, w, groups, eps).astype(
        y.dtype)


def passes(args, cfg, traffic, tiles=False):
    """Each pass alone, fused against its ``jax.numpy`` form."""
    import jax
    import jax.numpy as jnp

    from bigdl_tpu.ops.pallas import ssm_pointwise as pw
    b, s = int(traffic["batch_per_chip"]), int(traffic["seq_len"])
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, conv = h * p, h * p + 2 * g * n
    eps, k = cfg["layer_norm_epsilon"], cfg["conv_kernel"]
    bf16 = jnp.bfloat16
    key = jax.random.split(jax.random.PRNGKey(0), 10)
    xc = jax.random.normal(key[0], (b, s, conv), bf16)
    wc = 0.5 * jax.random.normal(key[1], (conv, k))
    bc = 0.5 * jax.random.normal(key[2], (conv,))
    dc = (jax.random.normal(key[3], (b, s, h, p), bf16),
          jax.random.normal(key[8], (b, s, g, n), bf16),
          jax.random.normal(key[9], (b, s, g, n), bf16))
    y = jax.random.normal(key[4], (b, s, h, p), bf16)
    z = jax.random.normal(key[5], (b, s, inner), bf16)
    wn = 1.0 + 0.1 * jax.random.normal(key[6], (inner,))
    dn = jax.random.normal(key[7], (b, s, inner), bf16)
    interpret = args.rehearsal
    c2, i2 = 2 * b * s * conv, 2 * b * s * inner
    split = dict(heads=h, head_dim=p, groups=g, state=n)

    def scan_layout(v):
        return v.swapaxes(1, 2)

    def fused_conv(x, w, bb):
        # x, B, C as the scan reads them: the swaps cancel the pass's own
        return tuple(scan_layout(o) for o in pw.conv_silu_split(
            x, w, bb, **split, interpret=interpret))

    def fused_norm(y_, z_, w_):
        return pw.gated_norm(scan_layout(y_), z_, w_, g, eps,
                             interpret=interpret)

    # (conv, its cotangents, norm, its inputs): each pass as the mixer
    # runs it, the fused ones in the scan's layouts
    forms = {"fused": (fused_conv, tuple(scan_layout(c) for c in dc),
                       fused_norm, (scan_layout(y), z, wn)),
             "xla": (functools.partial(_xla_conv, **split), dc,
                     functools.partial(_xla_norm, groups=g, eps=eps),
                     (y, z, wn))}
    got = {}
    variants = [("", None)]
    if tiles:
        variants = [(f" tokens {bt} chunk {ch} rows {br}", (bt, ch, br))
                    for bt, ch, br in ((128, 512, 256), (128, 256, 512),
                                       (512, 512, 1024), (256, 512, 512))]
    for label, tile in variants:
        if tile:
            pw.BLOCK_TOKENS, pw.CHUNK, pw.BLOCK_ROWS = tile
            for call in (pw._conv_fwd_call, pw._conv_bwd_call,
                         pw._norm_fwd_call, pw._norm_bwd_call):
                call.cache_clear()
        for name in (("fused",) if tile else ("fused", "xla")):
            cf, cts, nf, nargs = forms[name]
            # the tensors of T x C (T x I) each direction reads or writes
            for what, fn, fargs, ct, unit, tensors in (
                    ("conv+silu+split", cf, (xc, wc, bc), cts, c2, (2, 5)),
                    ("gated norm", nf, nargs, dn, i2, (3, 8))):
                fwd = jax.jit(fn)
                both = jax.jit(lambda *a, _f=fn, _c=ct: jax.vjp(_f, *a)[1](
                    _c))
                try:
                    _report(f"{what} {name}{label} forward",
                            _median_ms(fwd, fargs, args.repeats),
                            tensors[0] * unit)
                    _report(f"{what} {name}{label} forward+backward",
                            _median_ms(both, fargs, args.repeats),
                            tensors[1] * unit)
                    got[(what, name)] = (fwd(*fargs), both(*fargs))
                except Exception as e:        # a tile Mosaic refuses
                    print(f"{what} {name}{label}: {type(e).__name__}: "
                          f"{str(e)[:200]}", flush=True)
    back = {"conv+silu+split": (lambda o: tuple(scan_layout(v) for v in o),
                                lambda d: d),
            "gated norm": (lambda o: o,
                           lambda d: (scan_layout(d[0]),) + tuple(d[1:]))}
    for what, names in (("conv+silu+split", ("xBC", "w", "b")),
                        ("gated norm", ("y", "z", "w"))):
        if (what, "xla") not in got or (what, "fused") not in got:
            continue
        out_back, grad_back = back[what]
        (fo, fg), (xo, xg) = got[(what, "fused")], got[(what, "xla")]
        print(f"agree {what}: out "
              + ", ".join(f"{_rel(a, c):.2e}" for a, c in zip(
                  jax.tree.leaves(out_back(fo)), jax.tree.leaves(xo)))
              + "; " + ", ".join(f"d{n} {_rel(a, c):.2e}" for n, a, c in
                                 zip(names, grad_back(fg), xg)), flush=True)
    bound = pw.pointwise_bytes(b * s, inner, conv, 2) / HBM_BYTES_PER_S
    print(f"bound a layer-step (forwards twice, backwards once): "
          f"{1e3 * bound:.3f} ms", flush=True)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def layer(args, cfg, traffic):
    """The whole mixer, fused, unfused and (``--parent``) the parent's."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import pytest

    from bigdl_tpu.nn import state_space
    from bigdl_tpu.ops.pallas import ssd as kernels
    from bigdl_tpu.ops.pallas import ssm_pointwise as pw
    b, s = int(traffic["batch_per_chip"]), int(traffic["seq_len"])
    modules = {"fused": state_space, "unfused": state_space}
    if args.parent:
        modules["parent"] = _load(args.parent, "state_space_parent")

    def paths(stack, name):
        mp = stack.enter_context(pytest.MonkeyPatch.context())
        if args.rehearsal:
            mp.setattr(jax, "default_backend", lambda: "tpu")
            mp.setattr(kernels, "ssd", functools.partial(kernels.ssd,
                                                         interpret=True))
            for fn in ("conv_silu_split", "gated_norm"):
                mp.setattr(pw, fn, functools.partial(getattr(pw, fn),
                                                     interpret=True))
        if name == "unfused":
            mp.setattr(pw, "conv_silu_split", _xla_conv)
            mp.setattr(pw, "gated_norm", _xla_norm)

    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg["hidden_size"]),
                          jnp.bfloat16)
    runs = {}
    for name, mod in modules.items():
        with contextlib.ExitStack() as stack:
            paths(stack, name)
            m = mod.Mamba2Mixer(cfg["hidden_size"], cfg["mamba_num_heads"],
                                cfg["mamba_head_dim"], cfg["n_groups"],
                                cfg["ssm_state_size"], cfg["conv_kernel"],
                                cfg["chunk_size"],
                                cfg["layer_norm_epsilon"])
            params = jax.jit(m.init)(jax.random.PRNGKey(0))
            fwd = jax.jit(lambda p, v, _m=m: _m.apply(p, {}, v)[0])
            both = jax.jit(jax.value_and_grad(
                lambda p, v, _m=m: jnp.sum(_m.apply(p, {}, v)[0].astype(
                    jnp.float32) ** 2), argnums=(0, 1)))
            ms_f = _median_ms(fwd, (params, x), args.repeats)
            ms_b = _median_ms(both, (params, x), args.repeats)
            _report(f"mixer {name} forward", ms_f)
            _report(f"mixer {name} forward+backward", ms_b)
            print(f"mixer {name}: a layer-step under per_block "
                  f"(forward + forward+backward) {ms_f + ms_b:.3f} ms",
                  flush=True)
            runs[name] = (fwd(params, x), both(params, x))
            if args.profile and not args.rehearsal:
                _profile(args.profile, name, both, (params, x))
    base = runs["unfused"]
    for name in runs:
        if name == "unfused":
            continue
        out, (_, (gp, gx)) = runs[name]
        print(f"agree mixer {name} vs unfused: out {_rel(out, base[0]):.2e}, "
              f"dx {_rel(gx, base[1][1][1]):.2e}, "
              + ", ".join(f"d{k} {_rel(gp[k], base[1][1][0][k]):.2e}"
                          for k in sorted(gp)), flush=True)


def _profile(root, name, fn, args):
    import jax

    from benchmarks import trace_reduce, xplane
    path = os.path.join(root, name)
    os.makedirs(path, exist_ok=True)
    with jax.profiler.trace(path):
        for _ in range(3):
            jax.block_until_ready(fn(*args))
    events = xplane.read_events(xplane.find_xplane(path))
    planes = trace_reduce.device_planes(events)
    window = trace_reduce.extent(events, planes)
    for op, secs in trace_reduce.top_ops(events, window, n=25,
                                         planes=planes[:1]):
        print(f"profile {name}: {1e3 * secs / 3:9.3f} ms a step  {op}",
              flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--parent", default="")
    ap.add_argument("--tiles", action="store_true")
    ap.add_argument("--profile", default="")
    ap.add_argument("--only", choices=("passes", "layer"), default="")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu" and not args.rehearsal:
        sys.exit("dev/ssm_pointwise.py: no TPU")
    from benchmarks import model_setup
    cfg, traffic = _cell(args.rehearsal)
    model_setup.set_dtype_policy(cfg["policy"])
    if args.only in ("", "passes"):
        passes(args, cfg, traffic)
    if args.only in ("", "layer"):
        layer(args, cfg, traffic)
    if args.tiles:
        passes(args, cfg, traffic, tiles=True)


if __name__ == "__main__":
    main()
