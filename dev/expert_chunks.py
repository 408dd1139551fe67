"""Where ``parallel.expert.ExpertShare`` should cut its sorted rows, on
the chip:

    chiprun -- python3 dev/expert_chunks.py

The layer alone (no shared expert), forward + backward in one jitted
``value_and_grad`` as a recomputed block's step runs it, at the two
expert cells' shapes and dtype policy, under the routings the cells
meet:

- ``balanced``: random router, unit-variance tokens (the kimi cell);
- ``collapsed j``: every token picks the SAME k experts, j of them held
  (the keye cell at random weights: the live rows are j T), EACH j with
  its time and its chance when k of ``experts_total`` are drawn, and the
  mean weighted by chance;

for each schedule:

- ``two spans``: the module's (PR 36): a first span of T k / n rows for
  each n of ``--divisions`` (the module's own rule among them) and ONE
  second span of the rest, run when the live rows pass the first: two
  bodies in the compiled step whatever n;
- ``equal chunks``: the module's until PR 36, kept HERE alone: T k / n
  rows each for each n of ``--equal``, a body each (n = 4 is the twice-
  the-share schedule PR 34 timed and left out for its four bodies).

Beside each time: the spans or chunks run and the live rows' share of
their rows. ``--parent`` also times, at the module's own cut, the layer
with the rows for experts elsewhere put into the last held expert's
group (commit 0aa00c8's products over every row of a span). PERF.md
section 6 (PRs 34, 36) quotes it; the numbers also go to
``chiprun_out/expert_chunks.json``. Refuses to run without a TPU;
``--rehearsal`` walks the same code at the data files' tiny widths on
any backend and is never a result (the JSON says ``rehearsal``).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELLS = ("kimi-vl-a3b-instruct.train.seq8192",
         "keye-vl-2.0-30b-a3b.train.seq16384")


def _ms(fn, *args, iters=10):
    import jax
    jax.block_until_ready(fn(*args))          # compiles
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / iters


def _layer(cfg):
    """The cell's ``ExpertShare`` without its shared expert, and
    (held, total, k, offset)."""
    from bigdl_tpu.parallel import expert
    if cfg["builder"] == "kimi":
        held, total = cfg["n_routed_experts"], \
            cfg["published"]["n_routed_experts"]
        kw = dict(scoring="sigmoid", route_scale=cfg["routed_scaling_factor"],
                  bias_update_rate=cfg["bias_update_rate"])
    else:
        held, total, kw = cfg["num_experts"], \
            cfg["published"]["num_experts"], {}
    k, off = cfg["num_experts_per_tok"], cfg["experts_offset"]
    return expert.ExpertShare(
        cfg["hidden_size"], cfg["moe_intermediate_size"], total, k,
        experts_held=held, experts_offset=off, **kw), (held, total, k, off)


def _chance(j, held, total, k):
    """j of the k experts every token picks are held, the k drawn from
    ``total`` without favour."""
    return math.comb(held, j) * math.comb(total - held, k - j) \
        / math.comb(total, k)


def _equal_chunks():
    """``_in_chunks`` as it was until PR 36: EQUAL chunks of ``rows``
    sorted rows, the first always, each later one behind a ``lax.cond``
    of its own (a body each in the compiled step)."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
    def in_chunks(chunk, rows, weights, tokens, cw, where, live):
        y = chunk(weights, tokens, cw, where, lo=0, rows=rows)
        for lo in range(rows, where[0].shape[0], rows):
            y = jax.lax.cond(
                live > lo,
                lambda y, lo=lo: y + chunk(weights, tokens, cw, where, lo=lo,
                                           rows=rows),
                lambda y: y, y)
        return y

    def bwd(chunk, rows, res, dy):
        weights, tokens, cw, where, live = res

        def grads(lo):
            return jax.vjp(lambda *a: chunk(*a, where, lo=lo, rows=rows),
                           weights, tokens, cw)[1](dy)

        acc = grads(0)
        for lo in range(rows, where[0].shape[0], rows):
            acc = jax.lax.cond(
                live > lo,
                lambda acc, lo=lo: jax.tree.map(jnp.add, acc, grads(lo)),
                lambda acc: acc, acc)
        return (*acc, None, None)

    in_chunks.defvjp(lambda chunk, rows, *args: (
        in_chunks(chunk, rows, *args), args), bwd)
    return in_chunks


def cell_alone(name, divisions, equal, parent, rehearsal):
    import jax
    import jax.numpy as jnp
    from benchmarks import manifest, model_setup
    from bigdl_tpu.parallel import expert
    cell = manifest.data_file("workloads", name)
    cfg = manifest.data_file("configs", cell["config"])
    traffic = manifest.data_file("traffic", cell["traffic"])
    if rehearsal:
        cfg, traffic = (dict(x, **x["rehearsal"]) for x in (cfg, traffic))
    model_setup.set_dtype_policy(cfg["policy"])
    tokens = int(traffic["seq_len"]) * int(traffic["batch_per_chip"])
    layer, (held, total, k, off) = _layer(cfg)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (1, tokens, cfg["hidden_size"]), jnp.float32)
    all_rows = tokens * k
    own = expert._chunk_rows(all_rows, held, total,
                             layer.bias_update_rate is not None)
    print(f"{name}: {tokens} tokens, {held} of {total} experts, {k} a token, "
          f"{all_rows} sorted rows, the module's first span {own}",
          flush=True)

    def loss(p, x):
        y, state = layer.apply(p, layer.init_state(), x, training=True)
        return jnp.sum(y.astype(jnp.float32) ** 2), state

    # every token's first feature carries what steers the router
    steered = x.at[..., 0].set(4.0)
    routings = [("balanced", params, x, 1.0)]
    away = [e for e in range(total) if not off <= e < off + held]
    for j in range(min(k, held) + 1):
        chosen = list(range(off, off + j)) + away[:k - j]
        p = dict(params, router_weight=params["router_weight"].at[
            jnp.asarray(chosen), 0].set(3.0))
        routings.append((f"collapsed {j}", p, steered,
                         _chance(j, held, total, k)))
    found = []
    products = [("", expert.grouped_matmul)]
    if parent:
        def riders(x, w, group_sizes, real=expert.grouped_matmul):
            return real(x, w, group_sizes.at[held - 1].add(group_sizes[held])
                        .at[held].set(0))
        products.append((" (products over every row)", riders))
    schedules = [("two spans", rows, said, product)
                 for rows in sorted({all_rows // n for n in divisions
                                     if all_rows % n == 0} | {own},
                                    reverse=True)
                 for said, product in (products if rows == own
                                       else products[:1])]
    schedules += [("equal chunks", all_rows // n, *products[0])
                  for n in equal if all_rows % n == 0]
    real_rule, real_cut = expert._chunk_rows, expert._in_chunks
    for schedule, rows, said, product in schedules:
        expert._chunk_rows = lambda *a, rows=rows: rows
        expert._in_chunks = real_cut if schedule == "two spans" \
            else _equal_chunks()
        expert.grouped_matmul = product
        both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))
        expected = 0.0
        which = "the first" if schedule == "two spans" else "each"
        print(f"  {schedule}, {which} {rows} rows "
              f"({rows * total / (all_rows * held):.2f} x the balanced "
              f"share){said}", flush=True)
        for what, p, tok, chance in routings:
            state = jax.device_get(both(p, tok)[0][1])
            ms = _ms(both, p, tok)
            live = round(float(state["moe_local_assignment_share"])
                         * all_rows)
            # the module's two keys describe ITS cut: count the equal
            # chunks here
            ran = max(1, -(-live // rows)) if schedule == "equal chunks" \
                else int(state["moe_chunks_run"])
            worked_on = ran * rows if schedule == "equal chunks" \
                else (all_rows if ran == 2 else rows)
            if what != "balanced":
                expected += chance * ms
            found.append(dict(
                cell=name, schedule=schedule, chunk_rows=rows, routing=what,
                products_over_every_row=bool(said), ms=ms, chance=chance,
                live_rows=live, ran=ran, rows_worked_on=worked_on))
            print(f"    {what:12s}: forward+backward {ms:8.3f} ms, live "
                  f"rows {live}, ran {ran}, live / rows worked on "
                  f"{live / worked_on:.4f}"
                  + ("" if what == "balanced"
                     else f", chance {chance:.4f}"), flush=True)
        print(f"    collapsed, weighted by chance: {expected:8.3f} ms",
              flush=True)
        found.append(dict(cell=name, schedule=schedule, chunk_rows=rows,
                          routing="collapsed, weighted",
                          products_over_every_row=bool(said), ms=expected))
    expert._chunk_rows, expert._in_chunks = real_rule, real_cut
    expert.grouped_matmul = products[0][1]
    return found


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--divisions", default="2,4,8",
                    help="two spans: the first is T k / n rows, for each "
                         "n that divides them")
    ap.add_argument("--equal", default="4",
                    help="equal chunks: n of T k / n rows each")
    ap.add_argument("--parent", action="store_true")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu" and not args.rehearsal:
        sys.exit("dev/expert_chunks.py: no TPU")
    found = []
    for name in args.cells.split(","):
        found += cell_alone(name, *([int(n) for n in ns.split(",") if n]
                                    for ns in (args.divisions, args.equal)),
                            args.parent, args.rehearsal)
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "expert_chunks.json"), "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "rehearsal": args.rehearsal, "found": found}, f, indent=1)


if __name__ == "__main__":
    main()
