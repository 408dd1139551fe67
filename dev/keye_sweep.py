"""Time the Keye cell's kernels on the chip, one line each.

``chiprun -- python3 dev/keye_sweep.py`` runs, at the benchmark cell's
per-layer shape (one sequence of 16384 tokens, 32 query / 4 key-value
heads of 128, 16 indexer heads of 64, top-2048; 16 experts of 2048 x 768
on 32768 sorted rows, half a chunk of ``ExpertShare``'s): the six kernels of
``ops/pallas/sparse_attention.py`` one by one (the scores kernel also as
the backward pass runs it, masking again), the exact selection beside
``lax.top_k`` of the same rows (a sort; ``approx_max_k`` is not the
model), a gather and a scatter-add of the experts' rows, and the
grouped matrix products of ``parallel/expert.py`` through megablox and
through ``lax.ragged_dot``, forward and backward, at the expected load
and with every assignment on one expert. Prints milliseconds a call.
PERF.md section 6 (PR 31) quotes it. Refuses to run without a TPU.
"""
from __future__ import annotations

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


def _same_where_read(again, kept, sched):
    """The masked scores written again equal the selection's array on
    every column a key step of ``sched.bk`` reaches from a row."""
    import jax.numpy as jnp
    s = kept.shape[-1]
    reach = (jnp.arange(s)[:, None] // sched.bq * sched.bq + sched.bq - 1) \
        // sched.bk * sched.bk + sched.bk
    read = jnp.arange(s)[None, :] < reach
    return jnp.all(jnp.where(read, again == kept, True))


def main():
    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        sys.exit("dev/keye_sweep.py: no TPU")
    from bigdl_tpu.ops.pallas import sparse_attention as sa
    from bigdl_tpu.parallel import expert

    s, g, per, d, j, di, topk = 16384, 4, 8, 128, 16, 64, 2048
    bf, f32 = jnp.bfloat16, jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q = jax.random.normal(ks[0], (g, per, s, d), bf)
    k = jax.random.normal(ks[1], (g, s, d), bf)
    v = jax.random.normal(ks[2], (g, s, d), bf)
    qi = jax.random.normal(ks[3], (1, j, s, di), f32)
    ki = jax.random.normal(ks[4], (1, s, di), f32)
    wi = jax.random.normal(ks[5], (1, s, j), f32) * (j * di) ** -0.5
    scale = d ** -0.5

    def line(name, ms, note=""):
        print(f"{name:44s} {ms:9.2f} ms  {note}", flush=True)

    scores_fn = jax.jit(lambda a, b, c: sa.index_scores(a, b, c))
    line("index_scores (float32 as bf16 halves)", _ms(scores_fn, qi, ki, wi))
    scores = scores_fn(qi, ki, wi)
    select_fn = jax.jit(lambda x: sa.select_rows(x, topk))
    line("select_rows (bisection, exact)", _ms(select_fn, scores + 0.0))
    slab = jnp.where(jnp.arange(s)[None, :] <= jnp.arange(2048)[:, None]
                     + (s - 2048), scores[0, -2048:], -jnp.inf)
    line("lax.top_k k=2048, the LAST 2048 rows only",
         _ms(jax.jit(lambda x: jax.lax.top_k(x, topk)[0][:, -1]), slab),
         "x 8 for a layer")
    kept, lse_i, thr, tie = select_fn(scores + 0.0)
    masked_fn = jax.jit(lambda a, b, c, t, m: sa.index_scores(
        a, b, c, keep=(t, m)))
    same = _same_where_read(masked_fn(qi, ki, wi, thr, tie), kept,
                            sa.sparse_schedule(s))
    line("index_scores, masked again (the backward's)",
         _ms(masked_fn, qi, ki, wi, thr, tie),
         f"equal to select_rows' wherever attend reads: {bool(same)}")
    attend_fn = jax.jit(lambda *a: sa.attend(*a, scale=scale))
    fwd = _ms(attend_fn, q, k, v, kept)
    pairs = s * (s + 1) // 2
    line("attend (forward)", fwd,
         f"{4 * pairs * d * g * per / fwd / 1e9:.1f} TFLOP/s of causal pairs")
    o, lse = attend_fn(q, k, v, kept)
    sched = sa.sparse_schedule(s, topk)
    bwd_call = sa._attend_bwd_call(g, g, per, s, d, bf, scale, sched.bq,
                                   sched.bk, False)
    delta = jnp.sum(o.astype(f32) ** 2, -1)
    bwd = _ms(jax.jit(bwd_call), q, k, v, kept, o, lse, delta)
    line("attend backward (one pass)", bwd,
         f"{10 * pairs * d * g * per / bwd / 1e9:.1f} TFLOP/s of causal pairs")
    probs_call = sa._probs_call(1, g, per, s, d, scale, sched.index_bq,
                                sched.index_bk, 1.0 / s, False)
    line("kept_probs (d L_I / d I)",
         _ms(jax.jit(lambda *a: probs_call(*a) + 0.0), q, k, kept + 0.0, lse,
             lse_i))
    d_scores = probs_call(q, k, kept + 0.0, lse, lse_i)
    ib_call = sa._index_bwd_call(1, s, j, di, sched.index_bq, sched.index_bk,
                                 bf,
                                 False)
    line("index_backward", _ms(jax.jit(ib_call), qi, ki, wi, d_scores))

    # the experts: 32768 sorted assignment rows (4096 tokens' worth)
    t, kk, e, dm, f = 4096, 8, 16, 2048, 768
    rows = jax.random.normal(ks[6], (t * kk, dm), bf)
    w = jax.random.normal(ks[7], (e, f, dm), bf) * dm ** -0.5
    loads = {"expected (256 rows an expert)":
             jnp.asarray([256] * e + [t * kk - 256 * e], jnp.int32),
             "every row on one expert":
             jnp.asarray([t * kk] + [0] * e, jnp.int32)}

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda x, m, n: jnp.sum(fn(x, m, n).astype(f32)),
            argnums=(0, 1)))

    index = jax.random.randint(ks[0], (t * kk,), 0, t)
    tokens = rows[:t]
    line("gather 32768 rows of 2048 from 4096",
         _ms(jax.jit(lambda x, i: jnp.take(x, i, axis=0)), tokens, index))
    line("gather 8192 rows of 2048 from 4096",
         _ms(jax.jit(lambda x, i: jnp.take(x, i, axis=0)), tokens,
             index[:8192]))
    line("scatter-add 8192 rows of 2048 (float32) into 4096",
         _ms(jax.jit(lambda y, i: jnp.zeros((t, dm), f32).at[i].add(
             y.astype(f32))), rows[:8192], index[:8192]))
    for name, sizes in loads.items():
        line(f"megablox gmm fwd+bwd, {name}",
             _ms(both(expert.grouped_matmul), rows, w, sizes))
        line(f"lax.ragged_dot fwd+bwd, {name}",
             _ms(both(lambda x, m, n: jax.lax.ragged_dot(
                 x, m.swapaxes(1, 2), n[:-1], preferred_element_type=bf)),
                 rows, w, sizes))


if __name__ == "__main__":
    main()
