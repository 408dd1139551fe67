"""What a chunk of ``parallel.expert.ExpertShare`` should be, on the chip:

    chiprun -- python3 dev/expert_chunks.py

The layer alone (no shared expert), forward + backward in one jitted
``value_and_grad`` as a recomputed block's step runs it, at the two
expert cells' shapes and dtype policy, for each way ``--divisions`` cuts
the T k sorted assignment rows into chunks, under the routings the cells
meet:

- ``balanced``: random router, unit-variance tokens (the kimi cell);
- ``collapsed j``: every token picks the SAME k experts, j of them held
  (the keye cell at random weights: the live rows are j T), weighted by
  the chance of each j when k of ``experts_total`` are drawn.

Beside each time: ``moe_chunks_run`` and ``moe_product_row_share`` as
the layer's state gives them. ``--parent`` also times, at the module's
own chunk, the layer with the rows for experts elsewhere put into the
last held expert's group (commit 0aa00c8's products over every row of a
chunk). PERF.md section 6 (PR 34) quotes it; the numbers also go to
``chiprun_out/expert_chunks.json``. Refuses to run without a TPU;
``--rehearsal`` walks the same code at the data files' tiny widths on
any backend and is never a result.
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


def cell_alone(name, divisions, parent, rehearsal):
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
    own = expert._chunk_rows(tokens * k, held, total)
    print(f"{name}: {tokens} tokens, {held} of {total} experts, {k} a token, "
          f"{tokens * k} sorted rows, the module's chunk {own}", flush=True)

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
    chunks = sorted({tokens * k // n for n in divisions
                     if tokens * k % n == 0} | {own}, reverse=True)
    products = [("", expert.grouped_matmul)]
    if parent:
        def riders(x, w, group_sizes, real=expert.grouped_matmul):
            return real(x, w, group_sizes.at[held - 1].add(group_sizes[held])
                        .at[held].set(0))
        products.append((" (products over every row)", riders))
    real_rule = expert._chunk_rows
    for rows in chunks:
        for said, product in products if rows == own else products[:1]:
            expert._chunk_rows = lambda *a, rows=rows: rows
            expert.grouped_matmul = product
            both = jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                              has_aux=True))
            expected = 0.0
            print(f"  chunks of {rows} rows "
                  f"({rows * total / (tokens * k * held):.2f} x the balanced "
                  f"share){said}", flush=True)
            for what, p, tok, chance in routings:
                state = jax.device_get(both(p, tok)[0][1])
                ms = _ms(both, p, tok)
                if what != "balanced":
                    expected += chance * ms
                found.append(dict(
                    cell=name, chunk_rows=rows, routing=what,
                    products_over_every_row=bool(said), ms=ms,
                    chance=chance, **{key: float(state[key]) for key in (
                        "moe_local_assignment_share", "moe_chunks_run",
                        "moe_product_row_share")}))
                print(f"    {what:12s}: forward+backward {ms:8.3f} ms, live "
                      f"share {float(state['moe_local_assignment_share']):.4f}"
                      f", chunks run {float(state['moe_chunks_run']):.0f}, "
                      f"rows multiplied / rows of those chunks "
                      f"{float(state['moe_product_row_share']):.4f}"
                      + ("" if what == "balanced"
                         else f", chance {chance:.4f}"),
                      flush=True)
            print(f"    collapsed, weighted by chance: {expected:8.3f} ms",
                  flush=True)
            found.append(dict(cell=name, chunk_rows=rows,
                              routing="collapsed, weighted",
                              products_over_every_row=bool(said),
                              ms=expected))
    expert._chunk_rows, expert.grouped_matmul = real_rule, products[0][1]
    return found


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--divisions", default="2,4,6,8",
                    help="chunks the T k rows are cut into (those that "
                         "divide them)")
    ap.add_argument("--parent", action="store_true")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu" and not args.rehearsal:
        sys.exit("dev/expert_chunks.py: no TPU")
    found = []
    for name in args.cells.split(","):
        found += cell_alone(name, [int(n) for n in args.divisions.split(",")],
                            args.parent, args.rehearsal)
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "expert_chunks.json"), "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "rehearsal": args.rehearsal, "found": found}, f, indent=1)


if __name__ == "__main__":
    main()
