"""Pipeline parallelism: SPMD pipeline schedules over a mesh axis.

The reference has no pipeline parallelism (whole model per executor). The
TPU-native construction (the scaling-book recipe): L IDENTICAL layers are
stacked parameter-wise, the stack is sharded over a mesh axis so each
device owns L/S consecutive layers, and microbatches stream through the
stages with activations hopping stage-to-stage via ``ppermute`` (neighbor
ICI links). All devices run the same program — stage identity comes from
``lax.axis_index`` — so the whole thing jits as one SPMD computation.

Homogeneity is the honest constraint: heterogeneous ``Sequential`` stages
cannot ride one SPMD program. That matches where pipelining earns its keep
(deep stacks of identical blocks).

Two layers of machinery live here:

- :func:`pipeline_apply` — the original forward-only GPipe fill-drain
  apply (autodiff produces the reverse pipeline), kept for inference-style
  uses and as the simplest construction.
- The **schedule machinery** (ISSUE 11): explicit unit-level schedules
  (``gpipe`` / ``1f1b`` / ``interleaved_1f1b``) generated as per-device
  ordered (forward | backward, chunk, microbatch) unit lists, an exact
  event simulation that derives each schedule's bubble fraction and
  activation-stash bound, a *measured* bubble fraction that feeds real
  per-stage span timings through the same dependency graph
  (:func:`measure_pipeline_bubble`), and :class:`PipelineParallel` — the
  production train-step construction ``DistriOptimizer`` drives
  (``pipeline_stages=`` / ``set_pipeline()``): one compiled step that
  scans the combined forward/backward schedule with manual per-chunk
  ``jax.vjp``, a bounded activation stash, gradients accumulated in
  donated scan carries, and the optimizer update firing exactly once per
  accumulated step — the same microbatching contract as
  ``set_grad_accumulation(k)`` (optim/accumulation.py).

Schedule cost model (docs/PERFORMANCE.md has the table):

- ``gpipe``       — all forwards then all backwards; bubble fraction
                    (S-1)/(M+S-1); every one of the M microbatches'
                    activations is live at the turnaround (stash M).
- ``1f1b``        — steady-state one-forward-one-backward; the SAME
                    bubble fraction (S-1)/(M+S-1) — the schedule's win is
                    the activation stash, bounded by ~S in-flight
                    microbatches instead of M, independent of M.
- ``interleaved_1f1b`` — each device owns ``v`` non-contiguous chunks of
                    L/(S*v) layers (round-robin placement); fill/drain
                    shrinks by v: bubble fraction (S-1)/(v*M+S-1) —
                    STRICTLY below GPipe's for v > 1 at the same (S, M),
                    at the cost of v-1 extra inter-stage hops per
                    microbatch. This is the schedule the bench row's
                    measured receipt compares against GPipe.
"""
from __future__ import annotations

import logging
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.parallel.collective import shard_map
from bigdl_tpu.parallel.engine import get_mesh

logger = logging.getLogger("bigdl_tpu.parallel")

__all__ = ["pipeline_apply", "stack_layer_params",
           "pipeline_schedule_stats", "PIPELINE_SCHEDULES",
           "check_pipeline_schedule", "pipeline_schedule_order",
           "PipelineSchedule", "simulate_schedule",
           "measure_pipeline_bubble", "partition_sequential",
           "PipelineParallel"]

PIPELINE_SCHEDULES = ("gpipe", "1f1b", "interleaved_1f1b")


def check_pipeline_schedule(name: str) -> str:
    name = "1f1b" if name is None else str(name)
    if name not in PIPELINE_SCHEDULES:
        raise ValueError(f"unknown pipeline schedule {name!r} "
                         f"(known: {list(PIPELINE_SCHEDULES)})")
    return name


def stack_layer_params(params_list):
    """Stack per-layer param pytrees into one tree with a leading layer
    axis (what ``pipeline_apply`` consumes and what gets sharded)."""
    return jax.tree.map(lambda *ls: jnp.stack(ls), *params_list)


# ---------------------------------------------------------------------------
# schedule generation: per-device ordered (kind, chunk, microbatch) units
# ---------------------------------------------------------------------------

@dataclass
class PipelineSchedule:
    """One generated schedule: per-device unit orders plus the exact
    unit-tick timeline properties derived from them. ``orders[d]`` is
    device ``d``'s execution order of ``("F"|"B", global_chunk, mb)``
    units; ``starts`` maps each unit to its unit-tick start. Windows are
    the exact buffer bounds the SPMD executor sizes its stash with."""
    num_microbatches: int
    n_stages: int
    schedule: str
    virtual_stages: int
    orders: list = field(repr=False)
    starts: dict = field(repr=False)
    makespan: int = 0
    bubble_fraction: float = 0.0
    peak_stash_microbatches: int = 0
    act_window: int = 1
    cot_window: int = 1

    @property
    def n_chunks(self) -> int:
        return self.n_stages * self.virtual_stages


def _list_schedule(orders, n_chunks, fwd_s, bwd_s):
    """Earliest-start timing of fixed per-device unit orders under the
    pipeline dependency DAG: ``F(g, m)`` needs ``F(g-1, m)``; ``B(g,
    m)`` needs ``F(g, m)`` and ``B(g+1, m)`` (the cotangent flows
    downstream; the last chunk's backward seeds from the loss). Returns
    (starts, done, makespan, busy_per_device)."""
    done: dict = {}
    starts: dict = {}
    free = [0.0] * len(orders)
    ptr = [0] * len(orders)
    total = sum(len(o) for o in orders)
    placed = 0
    while placed < total:
        progressed = False
        for d, order in enumerate(orders):
            while ptr[d] < len(order):
                kind, g, mb = order[ptr[d]]
                deps = ([("F", g - 1, mb)] if g > 0 else []) \
                    if kind == "F" else \
                    [("F", g, mb)] + ([("B", g + 1, mb)]
                                      if g < n_chunks - 1 else [])
                if any(u not in done for u in deps):
                    break
                start = max([free[d]] + [done[u] for u in deps])
                dur = fwd_s[d] if kind == "F" else bwd_s[d]
                starts[(kind, g, mb)] = start
                done[(kind, g, mb)] = start + dur
                free[d] = start + dur
                ptr[d] += 1
                placed += 1
                progressed = True
        if not progressed:
            raise RuntimeError("pipeline schedule deadlocked — invalid "
                               "unit order")
    busy = [sum(fwd_s[d] if k == "F" else bwd_s[d] for k, _, _ in o)
            for d, o in enumerate(orders)]
    return starts, done, max(free), busy


def pipeline_schedule_order(num_microbatches: int, n_stages: int,
                            schedule: str = "1f1b",
                            virtual_stages: int = 1) -> PipelineSchedule:
    """Generate the unit-level schedule as explicit per-device orders.

    - ``gpipe``: all forwards in microbatch order, then all backwards in
      REVERSE microbatch order (what autodiff of the forward fill-drain
      scan produces). ``virtual_stages`` must be 1.
    - ``1f1b`` (PipeDream-flush): device ``d`` runs ``S-d-1`` warmup
      forwards, then steady one-forward-one-backward pairs, then drains
      backwards — backwards retire in microbatch order 0..M-1, matching
      ``set_grad_accumulation``'s j=0..k-1 gradient-add order, with at
      most ``S-d`` microbatches in flight (independent of M).
    - ``interleaved_1f1b`` (Megatron-style): each device owns ``v``
      round-robin chunks; microbatches advance in groups of S sweeping
      the chunks, warmup is ``2*(S-d-1) + (v-1)*S`` virtual steps, and
      the backward sweep mirrors the forward with chunks reversed.
      Requires M divisible by S.
    """
    m, s = int(num_microbatches), int(n_stages)
    v = int(virtual_stages)
    schedule = check_pipeline_schedule(schedule)
    if m < 1 or s < 1 or v < 1:
        raise ValueError(f"need microbatches/stages/virtual_stages >= 1, "
                         f"got M={m}, S={s}, v={v}")
    if schedule != "interleaved_1f1b" and v != 1:
        raise ValueError(f"virtual_stages={v} only applies to "
                         f"'interleaved_1f1b' (got {schedule!r})")
    c = s * v
    orders = []
    if schedule == "gpipe":
        for d in range(s):
            orders.append([("F", d, j) for j in range(m)]
                          + [("B", d, j) for j in reversed(range(m))])
    elif schedule == "1f1b":
        for d in range(s):
            w = min(m, s - d - 1)
            o = [("F", d, j) for j in range(w)]
            for j in range(m - w):
                o.append(("F", d, w + j))
                o.append(("B", d, j))
            o += [("B", d, j) for j in range(m - w, m)]
            orders.append(o)
    else:
        if m % s:
            raise ValueError(
                f"interleaved_1f1b advances microbatches in groups of "
                f"S: num_microbatches {m} must divide by {s} stages")

        def unit(d, k, forward):
            kg = k % c
            cl = kg // s
            if not forward:
                cl = v - 1 - cl
            mb = (k // c) * s + (kg % s)
            return ("F" if forward else "B", cl * s + d, mb)

        total = v * m
        for d in range(s):
            w = min(total, 2 * (s - d - 1) + (v - 1) * s)
            o = [unit(d, k, True) for k in range(w)]
            for j in range(total - w):
                o.append(unit(d, w + j, True))
                o.append(unit(d, j, False))
            o += [unit(d, j, False) for j in range(total - w, total)]
            orders.append(o)

    starts_f, done_f, makespan_f, _ = _list_schedule(
        orders, c, [1.0] * s, [1.0] * s)
    starts = {u: int(round(t)) for u, t in starts_f.items()}
    done = {u: int(round(t)) for u, t in done_f.items()}
    makespan = int(round(makespan_f))
    bubble = 1.0 - 2 * v * m / makespan

    # exact buffer windows: the activation stash slot of (g, mb) is live
    # from the upstream forward's completion (its own forward start for
    # chunk 0) until its backward completes; the cotangent slot from the
    # downstream backward's completion until its own backward completes.
    def _span(intervals):
        # minimal window W for mb-mod-W slot reuse: any two LIVE-AT-
        # THE-SAME-TIME microbatches must land on distinct slots, so W
        # exceeds the largest index gap among pairwise-overlapping
        # intervals
        worst = 1
        for i, (a_i, e_i) in intervals.items():
            for j, (a_j, e_j) in intervals.items():
                if j > i and a_j < e_i and a_i < e_j:
                    worst = max(worst, j - i + 1)
        return worst

    act_w, cot_w, peak = 1, 1, 1
    for g in range(c):
        acts = {}
        for mb in range(m):
            a = (done[("F", g - 1, mb)] if g > 0
                 else starts[("F", g, mb)])
            acts[mb] = (a, done[("B", g, mb)])
        act_w = max(act_w, _span(acts))
        live = [sum(1 for a, e in acts.values() if a <= tt < e)
                for tt in range(makespan)]
        peak = max(peak, max(live))
        if g < c - 1:
            cots = {mb: (done[("B", g + 1, mb)], done[("B", g, mb)])
                    for mb in range(m)}
            cot_w = max(cot_w, _span(cots))

    return PipelineSchedule(
        num_microbatches=m, n_stages=s, schedule=schedule,
        virtual_stages=v, orders=orders, starts=starts,
        makespan=makespan, bubble_fraction=bubble,
        peak_stash_microbatches=peak, act_window=act_w, cot_window=cot_w)


def pipeline_schedule_stats(num_microbatches: int, n_stages: int,
                            schedule: str = "gpipe", *,
                            virtual_stages: int = 1) -> dict:
    """Schedule cost as numbers instead of a docstring claim.

    ``schedule="gpipe"`` (the default) keeps the original fill-drain
    contract exactly — ``ticks`` = M+S-1 forward ticks, ``bubble_ticks``
    = S-1, ``bubble_fraction`` = (S-1)/(M+S-1) — the fraction is
    identical under combined forward+backward accounting, so the legacy
    fields stay honest. ``"1f1b"`` and ``"interleaved_1f1b"`` report the
    combined schedule: ``ticks`` is the fwd+bwd makespan in unit ticks,
    ``bubble_fraction`` the exact per-device idle share derived from the
    generated schedule (closed forms: (S-1)/(M+S-1) for 1f1b — equal to
    GPipe's, its win is the stash — and (S-1)/(v·M+S-1) for interleaved,
    strictly below GPipe's for v > 1). ``peak_stash_microbatches`` is
    the schedule's exact in-flight activation bound — the memory half of
    the story (GPipe: M; 1f1b: ~S, independent of M).
    """
    m, s = int(num_microbatches), int(n_stages)
    schedule = check_pipeline_schedule(schedule)
    if m < 1 or s < 1:
        raise ValueError(f"need microbatches >= 1 and stages >= 1, got "
                         f"M={m}, S={s}")
    sched = pipeline_schedule_order(m, s, schedule, virtual_stages)
    out = {"microbatches": m, "stages": s, "schedule": schedule,
           "virtual_stages": int(virtual_stages),
           "combined_ticks": sched.makespan,
           "peak_stash_microbatches": sched.peak_stash_microbatches}
    if schedule == "gpipe":
        ticks = m + s - 1
        out.update({"ticks": ticks, "bubble_ticks": s - 1,
                    "bubble_fraction": (s - 1) / ticks})
    else:
        out.update({"ticks": sched.makespan,
                    "bubble_ticks": sched.makespan
                    - 2 * int(virtual_stages) * m,
                    "bubble_fraction": sched.bubble_fraction})
    return out


def simulate_schedule(sched: PipelineSchedule, fwd_s, bwd_s) -> dict:
    """Timed list-scheduling of a generated schedule: every unit keeps
    its device's generated ORDER, starts as soon as its dependencies and
    its device allow, and lasts its device's measured span
    (``fwd_s[d]`` / ``bwd_s[d]`` seconds). Returns the makespan,
    per-device busy seconds, and the resulting bubble fraction — the
    *measured* bubble when the durations come from real per-stage span
    timings (:func:`measure_pipeline_bubble`)."""
    _, _, makespan, busy = _list_schedule(sched.orders, sched.n_chunks,
                                          fwd_s, bwd_s)
    return {"makespan_s": makespan, "busy_s": busy,
            "bubble_fraction":
                1.0 - sum(busy) / (sched.n_stages * makespan)}


def measure_pipeline_bubble(*, n_stages: int = 4, num_microbatches: int = 8,
                            virtual_stages: int = 2, d_model: int = 16,
                            mb_rows: int = 4, layers_per_stage: int = 2,
                            reps: int = 5, seed: int = 0,
                            schedules=PIPELINE_SCHEDULES) -> dict:
    """Measured pipeline bubble fractions from per-stage span timings.

    For each schedule, the per-unit work (one chunk's forward; one
    chunk's recompute+backward — the executor's honest backward cost) is
    built as the real jitted computation at this geometry and timed per
    stage (median of ``reps``, ``jax.device_get`` as the sync point —
    the sanctioned batched readback). The measured spans then drive the
    schedule's dependency graph through :func:`simulate_schedule`: the
    resulting bubble is what the schedule actually costs at the measured
    forward/backward ratio, not the unit-tick formula. (On a single-core
    CPU host the stages cannot physically overlap, so composing measured
    spans through the dependency graph is the honest way to read the
    parallel timeline; on a real mesh the same spans come from the
    per-stage trace.)

    Interleaved chunks hold ``layers_per_stage / virtual_stages`` layers
    each, so their units are measured separately — the comparison keeps
    total work identical across schedules. Returns per-schedule measured
    and modeled bubble fractions plus the raw spans.
    """
    import time as _time

    import numpy as np

    s, m, v = int(n_stages), int(num_microbatches), int(virtual_stages)
    if layers_per_stage % v:
        raise ValueError(f"layers_per_stage {layers_per_stage} not "
                         f"divisible by virtual_stages {v}")
    rng = np.random.default_rng(seed)
    x0 = jnp.asarray(rng.standard_normal((mb_rows, d_model))
                     .astype(np.float32))
    cot0 = jnp.asarray(rng.standard_normal((mb_rows, d_model))
                       .astype(np.float32))

    def _unit_fns(n_layers):
        params = [
            {"w": jnp.asarray((rng.standard_normal((d_model, d_model))
                               / np.sqrt(d_model)).astype(np.float32)),
             "b": jnp.zeros((d_model,), jnp.float32)}
            for _ in range(n_layers)]
        stacked = stack_layer_params(params)

        def chunk(p, h):
            def body(h, lp):
                return jnp.tanh(h @ lp["w"] + lp["b"]), None
            h, _ = jax.lax.scan(body, h, p)
            return h

        fwd = jax.jit(lambda h: chunk(stacked, h))

        def bwd(h, cot):
            y, vjp = jax.vjp(lambda p, hh: chunk(p, hh), stacked, h)
            return vjp(cot)
        return fwd, jax.jit(bwd)

    def _median_span(fn, *args):
        jax.device_get(jax.tree.leaves(fn(*args))[0])   # compile + warm
        spans = []
        for _ in range(max(int(reps), 1)):
            t0 = _time.perf_counter()
            out = fn(*args)
            jax.device_get(jax.tree.leaves(out)[0])
            spans.append(_time.perf_counter() - t0)
        return float(np.median(spans))

    spans_by_v: dict = {}
    for vv in sorted({1} | ({v} if "interleaved_1f1b" in schedules
                            else set())):
        fwd, bwd = _unit_fns(layers_per_stage // vv)
        tf_raw = [_median_span(fwd, x0) for _ in range(s)]
        tb_raw = [_median_span(bwd, x0, cot0) for _ in range(s)]
        # the stages are IDENTICAL computations (one SPMD program), so
        # per-stage sampling noise is not real heterogeneity — the
        # schedule is timed at the cross-stage median span (raw samples
        # reported); a genuinely imbalanced pipeline would feed its real
        # per-stage spans straight into simulate_schedule instead
        # (host floats throughout — the device sync happened inside
        # _median_span's device_get)
        tf = [sorted(tf_raw)[s // 2]] * s
        tb = [sorted(tb_raw)[s // 2]] * s
        spans_by_v[vv] = (tf, tb, tf_raw, tb_raw)

    out = {"n_stages": s, "num_microbatches": m, "virtual_stages": v,
           "geometry": f"d{d_model} mb{mb_rows} "
                       f"L{layers_per_stage}/stage", "schedules": {}}
    for name in schedules:
        vv = v if name == "interleaved_1f1b" else 1
        tf, tb, tf_raw, tb_raw = spans_by_v[vv]
        sched = pipeline_schedule_order(m, s, name, vv)
        sim = simulate_schedule(sched, tf, tb)
        out["schedules"][name] = {
            "measured_bubble_fraction": sim["bubble_fraction"],
            "modeled_bubble_fraction": pipeline_schedule_stats(
                m, s, name, virtual_stages=vv)["bubble_fraction"],
            "makespan_s": sim["makespan_s"],
            "fwd_span_s": tf[0], "bwd_span_s": tb[0],
            "fwd_span_samples_s": tf_raw, "bwd_span_samples_s": tb_raw,
            "virtual_stages": vv,
        }
    return out


# ---------------------------------------------------------------------------
# forward-only GPipe apply (the original construction, kept as-is)
# ---------------------------------------------------------------------------

def _local_stack_apply(layer_apply, local_params, x):
    """Run this stage's L/S stacked layers in sequence via lax.scan."""

    def body(h, layer_p):
        return layer_apply(layer_p, h), None

    y, _ = jax.lax.scan(body, x, local_params)
    return y


def pipeline_apply(layer_apply, stacked_params, x, *,
                   num_microbatches: int, axis: str = "model",
                   mesh: Mesh | None = None, data_axis: str | None = None,
                   with_stats: bool = False):
    """Apply L stacked identical layers to ``x`` through an S-stage
    pipeline over mesh ``axis``.

    ``layer_apply(layer_params, h) -> h`` is one layer's pure function;
    ``stacked_params`` leaves have leading dim L (see
    ``stack_layer_params``); L must divide by the axis size S, the batch
    by ``num_microbatches``. Differentiable end-to-end; returns the same
    result as serially applying the L layers (up to float order).

    ``data_axis`` composes the pipeline with data parallelism: the batch
    dim shards over that mesh axis and each data-parallel row of the mesh
    runs its own fill-drain pipeline over its batch shard (params stay
    pipeline-sharded, replicated across ``data_axis``).
    ``num_microbatches`` must then divide the per-row batch shard.

    ``with_stats=True`` returns ``(y, stats)`` where ``stats`` is
    :func:`pipeline_schedule_stats` for this run's (M, S) — the
    schedule's fill-drain bubble fraction (S-1)/(M+S-1) reported
    instead of hidden (tests/test_pipeline_parallel.py pins it).
    """
    mesh = mesh or get_mesh()
    s = mesh.shape[axis]
    n_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_layers % s:
        raise ValueError(f"{n_layers} layers not divisible by "
                         f"{s} pipeline stages")
    batch = x.shape[0]
    if data_axis is not None:
        d = mesh.shape[data_axis]
        if batch % d:
            raise ValueError(f"batch {batch} not divisible by "
                             f"data axis {d}")
        batch = batch // d           # per-row shard seen inside the body
    if batch % num_microbatches:
        raise ValueError(f"batch {batch} not divisible by "
                         f"{num_microbatches} microbatches")
    mb = batch // num_microbatches
    m = num_microbatches

    def body(local_params, xb):
        # local_params leaves: (L/S, ...) — this stage's layer block
        stage = jax.lax.axis_index(axis)
        mbs = xb.reshape((m, mb) + xb.shape[1:])
        perm = [(i, (i + 1) % s) for i in range(s)]  # downstream hop

        def tick(state, t):
            # lax.scan keeps the program size constant in M and S —
            # a Python unroll doubled the jaxpr per extra microbatch
            carry, out = state
            # stage 0 injects microbatch t; others take the upstream hop
            feed = jnp.take(mbs, jnp.minimum(t, m - 1), axis=0)
            h = jnp.where(stage == 0, feed, carry)
            y = _local_stack_apply(layer_apply, local_params, h)
            # the LAST stage finished microbatch t-(s-1) this tick
            oi = t - (s - 1)
            valid = (stage == (s - 1)) & (oi >= 0)
            slot = jnp.clip(oi, 0, m - 1)
            out = out.at[slot].set(
                jnp.where(valid, y, jnp.take(out, slot, axis=0)))
            carry = jax.lax.ppermute(y, axis, perm)
            return (carry, out), None

        init = (jnp.zeros_like(mbs[0]), jnp.zeros_like(mbs))
        (_, out), _ = jax.lax.scan(tick, init,
                                   jnp.arange(m + s - 1))
        # outputs are populated only on the last stage; psum replicates
        # them (zeros elsewhere keep the sum exact)
        out = jax.lax.psum(out, axis)
        return out.reshape((batch,) + out.shape[2:])

    pspec = jax.tree.map(lambda _: P(axis), stacked_params)
    xspec = P() if data_axis is None else P(data_axis)
    y = shard_map(
        body, mesh=mesh,
        in_specs=(pspec, xspec),
        out_specs=xspec,
        check_rep=False)(stacked_params, x)
    if with_stats:
        return y, pipeline_schedule_stats(m, s)
    return y


# ---------------------------------------------------------------------------
# production path: stage partitioning + the 1F1B train step construction
# ---------------------------------------------------------------------------

def partition_sequential(model, n_stages: int, virtual_stages: int = 1):
    """Validate a ``Sequential`` model for the pipeline path and return
    ``(template, n_layers, layers_per_chunk)``.

    The model's top-level children are the pipeline's layers: they must
    be structurally identical (same param tree structure, leaf shapes
    and dtypes — one SPMD program runs every stage) and stateless (a
    BatchNorm-style running stat cannot be updated consistently while
    microbatches are in flight on different stages). The layer count
    must divide by ``n_stages * virtual_stages``.
    """
    from bigdl_tpu.nn.containers import Sequential
    if not isinstance(model, Sequential):
        raise ValueError(
            f"pipeline_stages needs a Sequential model whose top-level "
            f"children are the pipeline layers, got "
            f"{type(model).__name__}")
    n_layers = len(model.modules)
    chunks = int(n_stages) * int(virtual_stages)
    if n_layers == 0 or n_layers % chunks:
        raise ValueError(
            f"{n_layers} top-level blocks not divisible by "
            f"{n_stages} stages x {virtual_stages} virtual stages")
    if model.params is None:
        raise ValueError("materialize() the model before pipelining")
    p0 = model.params["0"]
    struct0 = jax.tree.structure(p0)
    shapes0 = [(l.shape, jnp.dtype(l.dtype)) for l in jax.tree.leaves(p0)]
    for i in range(1, n_layers):
        pi = model.params[str(i)]
        if jax.tree.structure(pi) != struct0 or \
                [(l.shape, jnp.dtype(l.dtype))
                 for l in jax.tree.leaves(pi)] != shapes0:
            raise ValueError(
                f"pipeline stages must be structurally identical "
                f"blocks: child {i} differs from child 0 — wrap "
                "heterogeneous head/tail layers outside the pipelined "
                "stack")
    if jax.tree.leaves(model.state):
        raise ValueError(
            "pipeline_stages requires stateless blocks (running "
            "statistics like BatchNorm cannot be updated consistently "
            "while microbatches are in flight on different stages) — "
            "use LayerNorm-style normalization")
    return model.modules[0], n_layers, n_layers // chunks


class PipelineParallel:
    """Mechanics of the pipelined train step for one (mesh, model,
    criterion, optimizer) tuple: stage partitioning and parameter
    layout, state import/export (the checkpoint seam), and the combined
    forward/backward schedule step. ``DistriOptimizer`` owns the
    training loop; this class owns the layout and schedule algebra.

    Parameter layout: the L top-level blocks' params are stacked on a
    leading layer axis and PERMUTED device-major (device d's chunks
    contiguous, chunk-major within a device), then sharded over the
    ``pipe`` mesh axis — each device holds exactly its
    ``virtual_stages`` chunks of ``layers_per_chunk`` layers. Optimizer
    state rides the same layout (or per-stage bucket slices under the
    sharded-update composition), so checkpoints export back to the
    params-shaped model tree.
    """

    def __init__(self, mesh, model, criterion, optim, *,
                 n_stages: int, num_microbatches: int,
                 schedule: str = "1f1b", virtual_stages: int = 1,
                 axis: str = "pipe", data_axis: str | None = None,
                 remat_policy: str = "none",
                 sharded_update: bool = False,
                 bucket_mb: float | None = None):
        self.mesh = mesh
        self.axis = axis
        if axis not in mesh.axis_names:
            raise ValueError(
                f"pipeline_stages needs a {axis!r} mesh axis — build the "
                f"mesh with Engine.init(axes={{'data': N, {axis!r}: S}}) "
                f"(mesh has {mesh.axis_names})")
        self.s = int(mesh.shape[axis])
        if self.s != int(n_stages):
            raise ValueError(
                f"pipeline_stages={n_stages} but mesh axis {axis!r} has "
                f"size {self.s}")
        self.v = int(virtual_stages)
        self.schedule = check_pipeline_schedule(schedule)
        if self.schedule == "gpipe" and self.v != 1:
            raise ValueError("virtual_stages > 1 requires the "
                             "'interleaved_1f1b' schedule")
        self.m = int(num_microbatches)
        self.data_axis = (data_axis if data_axis in mesh.axis_names
                          else None)
        self.dp = (int(mesh.shape[self.data_axis])
                   if self.data_axis else 1)
        self.model = model
        self.criterion = criterion
        self.optim = optim
        self.remat_policy = remat_policy
        self.template, self.n_layers, self.lc = partition_sequential(
            model, self.s, self.v)
        # momentum/accumulator leaves carry mesh shardings on this path:
        # the concat-grouped small-leaf update miscompiles under GSPMD
        # (see SGD.group_small_leaves) — force the per-leaf form
        if getattr(optim, "group_small_leaves", False):
            optim.group_small_leaves = False
        for what in ("learning_rates", "weight_decays"):
            if getattr(optim, what, None) is not None:
                raise ValueError(
                    f"pipeline_stages stacks block params on a layer "
                    f"axis, so a params-shaped {what} tree cannot be "
                    "matched leafwise — use scalar hyperparameters")
        # device-major permutation: global stacked row order is
        # [device 0's chunks' layers, device 1's, ...] so a P('pipe')
        # sharding of the leading dim hands each device its own chunks
        self.perm = [g * self.lc + j
                     for d in range(self.s)
                     for cl in range(self.v)
                     for g in [cl * self.s + d]
                     for j in range(self.lc)]
        self.inv_perm = [0] * self.n_layers
        for pos, src in enumerate(self.perm):
            self.inv_perm[src] = pos
        self.sched = pipeline_schedule_order(self.m, self.s,
                                             self.schedule, self.v)
        self.repl = NamedSharding(mesh, P())
        self.stacked_shard = NamedSharding(mesh, P(axis))
        self._gather_jit = None
        self._export_jit = None
        # sharded-update composition: per-STAGE buckets over the local
        # stacked tree (identical across stages — reverse-topological
        # leaf order within the stage is preserved by GradientBuckets),
        # reduce-scattered over the data axis inside the step
        self.su_buckets = None
        if sharded_update:
            if self.data_axis is None or self.dp < 2:
                logger.info(
                    "pipeline + shard_weight_update: no data axis (or "
                    "size 1) on the mesh — nothing to shard the update "
                    "over, running the plain per-stage update")
            else:
                from bigdl_tpu.parameters.all_reduce import \
                    GradientBuckets
                if bucket_mb is None:
                    from bigdl_tpu.optim.sharded_update import \
                        tuned_bucket_mb
                    n_params = sum(
                        int(l.size) for l in jax.tree.leaves(model.params)
                    ) // self.s
                    bucket_mb = tuned_bucket_mb(n_params, self.dp)
                self.su_buckets = GradientBuckets(
                    self._local_template(),
                    bucket_bytes=int(float(bucket_mb) * (1 << 20)),
                    n_shards=self.dp)

    # ------------------------------------------------------------------
    # parameter / optimizer-state layout (the checkpoint seam)
    # ------------------------------------------------------------------
    def _stack(self, child_tree):
        """{'0': t0, ...} -> stacked tree, rows in device-major order."""
        return jax.tree.map(
            lambda *ls: jnp.stack([ls[i] for i in self.perm]),
            *[child_tree[str(i)] for i in range(self.n_layers)])

    def _local_template(self):
        """ShapeDtypeStructs of one device's local stacked tree."""
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                (self.v * self.lc,) + tuple(l.shape),
                jnp.dtype(l.dtype)),
            self.model.params["0"])

    def import_params(self, child_tree):
        return jax.device_put(self._stack(child_tree),
                              self.stacked_shard)

    def params_sharding(self):
        return self.stacked_shard

    def _unstack(self, stacked):
        """Stacked (device-major) tree -> {'0': t0, ...} child tree."""
        return {str(i): jax.tree.map(
            lambda l, pos=self.inv_perm[i]: l[pos], stacked)
            for i in range(self.n_layers)}

    def gather_params(self, stacked):
        """Step params state -> the model's per-child tree (for eval,
        ``model.sync`` and checkpoints)."""
        if self._gather_jit is None:
            def gather(st):
                full = jax.tree.map(
                    lambda l: jax.lax.with_sharding_constraint(
                        l, self.repl), st)
                return self._unstack(full)
            self._gather_jit = jax.jit(gather)
        return self._gather_jit(stacked)

    def _state_spec(self, st: dict) -> dict:
        pstruct = jax.tree.structure(self.model.params["0"])
        out = {}
        for k, v in st.items():
            if isinstance(v, dict) and k == "_su":
                out[k] = {bk: P((self.axis, self.data_axis))
                          for bk in v}
            elif isinstance(v, dict) and \
                    jax.tree.structure(v) == pstruct:
                out[k] = jax.tree.map(lambda _: P(self.axis), v)
            else:
                out[k] = P()
        return out

    def opt_state_sharding(self, st: dict) -> dict:
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self._state_spec(st),
            is_leaf=lambda s: isinstance(s, P))

    def import_opt_state(self, tree_state: dict) -> dict:
        """Params-shaped optimizer state (fresh ``init_state`` on the
        model tree, or a checkpoint) -> the step's stacked (or, under
        the sharded-update composition, per-stage bucket-slice)
        layout."""
        pstruct = jax.tree.structure(self.model.params)
        out = {}
        for k, val in tree_state.items():
            if k == "_su":   # already in step layout (warm re-import)
                out[k] = val
                continue
            if isinstance(val, dict) and \
                    jax.tree.structure(val) == pstruct:
                stacked = self._stack(val)
                if self.su_buckets is not None:
                    # per-stage flatten on the host, concatenated in
                    # device order: global vector (S * padded,), sharded
                    # over (pipe, data) — each device holds its stage's
                    # data-slice of every bucket
                    flats = {bk: [] for bk in self.su_buckets.keys}
                    for d in range(self.s):
                        local = jax.tree.map(
                            lambda l: l[d * self.v * self.lc:
                                        (d + 1) * self.v * self.lc],
                            stacked)
                        for bk, vec in \
                                self.su_buckets.flatten(local).items():
                            flats[bk].append(vec)
                    out.setdefault("_su", {})
                    for bk, parts in flats.items():
                        out["_su"][f"{k}.{bk}"] = jax.device_put(
                            jnp.concatenate(parts),
                            NamedSharding(self.mesh,
                                          P((self.axis,
                                             self.data_axis))))
                else:
                    out[k] = jax.device_put(stacked, self.stacked_shard)
            else:
                out[k] = jax.device_put(jnp.asarray(val), self.repl)
        return out

    def export_opt_state(self, st: dict) -> dict:
        """Step-layout optimizer state -> params-shaped trees (the
        ZeRO-1-compatible checkpoint layout shared with the rest of the
        stack); scalars pass through."""
        # ONE batched readback for the whole state tree (the export
        # runs at checkpoint/sync time, never in the step loop)
        host = jax.device_get(st)
        out = {}
        su = host.get("_su")
        for k, val in host.items():
            if k == "_su":
                continue
            out[k] = self._unstack(val) if isinstance(val, dict) else val
        if su is not None:
            # regroup {state_key.bucket: (S*padded,)} -> params-shaped
            by_state: dict = {}
            for name, vec in su.items():
                sk, bk = name.rsplit(".", 1)
                by_state.setdefault(sk, {})[bk] = vec
            for sk, bks in by_state.items():
                stages = []
                for d in range(self.s):
                    local = self.su_buckets.unflatten({
                        bk: vec.reshape(self.s, -1)[d]
                        for bk, vec in bks.items()})
                    stages.append(local)
                stacked = jax.tree.map(
                    lambda *ls: jnp.concatenate(
                        [jnp.asarray(l) for l in ls]), *stages)
                out[sk] = self._unstack(stacked)
        return out

    # ------------------------------------------------------------------
    # the pipelined train step
    # ------------------------------------------------------------------
    def _tick_tables(self):
        """Static (T, S) int32 schedule tables for the executor scan:
        this device's scheduled forward/backward unit per tick (local
        chunk + microbatch, -1 when idle) and the incoming activation /
        cotangent message's destination slot (written the tick AFTER the
        neighbor produced it — ppermute hops between ticks)."""
        import numpy as np

        T, s = self.sched.makespan, self.s
        fc = -np.ones((T, s), np.int32)
        fm = -np.ones((T, s), np.int32)
        bc = -np.ones((T, s), np.int32)
        bm = -np.ones((T, s), np.int32)
        ifc = -np.ones((T, s), np.int32)
        ifm = -np.ones((T, s), np.int32)
        ibc = -np.ones((T, s), np.int32)
        ibm = -np.ones((T, s), np.int32)
        c = self.sched.n_chunks
        for (kind, g, mb), t in self.sched.starts.items():
            d = g % s
            cl = g // s
            if kind == "F":
                fc[t, d], fm[t, d] = cl, mb
                if g + 1 < c and t + 1 < T:
                    dn = (g + 1) % s
                    ifc[t + 1, dn] = (g + 1) // s
                    ifm[t + 1, dn] = mb
            else:
                bc[t, d], bm[t, d] = cl, mb
                if g > 0 and t + 1 < T:
                    up = (g - 1) % s
                    ibc[t + 1, up] = (g - 1) // s
                    ibm[t + 1, up] = mb
        return tuple(jnp.asarray(a)
                     for a in (fc, fm, bc, bm, ifc, ifm, ibc, ibm))

    def _chunk_body(self, rng_mb):
        """One chunk's forward at microbatch key ``rng_mb``: scans the
        chunk's layers through the (stateless) template with the SAME
        per-child rng folds as ``Sequential.apply`` — dropout draws land
        exactly where the non-pipelined step's do."""
        from bigdl_tpu.nn.module import _fold
        from bigdl_tpu.optim.remat import (_checkpoint_policy,
                                           check_remat_policy)

        template, lc = self.template, self.lc
        state0 = self.model.state["0"]
        policy = check_remat_policy(self.remat_policy)

        def layer(h, xs):
            lp, gl = xs
            y, _ = template.apply(lp, state0, h, training=True,
                                  rng=_fold(rng_mb, gl))
            return y, None

        if policy != "none":
            # a layer is a block: "per_block" keeps what optim/remat.py's
            # keeps (KEPT_NAMES), spelled there alone
            layer = jax.checkpoint(layer, policy=_checkpoint_policy(policy))

        def chunk(p_chunk, x, g_global):
            # p_chunk leaves: (Lc, ...) — this chunk's layer block;
            # global child indices g_global*Lc .. +Lc-1 drive the folds
            gls = g_global * lc + jnp.arange(lc, dtype=jnp.int32)
            y, _ = jax.lax.scan(layer, x, (p_chunk, gls))
            return y

        return chunk

    def make_train_step(self, *, grad_clip=None, input_transform=None):
        """Build ``step(params, mstate, opt_state, rng, data, labels,
        epoch) -> (params, mstate, opt_state, loss)`` — one compiled
        program scanning the combined forward/backward schedule.

        Per tick each stage deposits the neighbor hops that arrived,
        runs its scheduled forward unit (chunk input from the bounded
        activation stash; stage 0 injects the strided microbatch), runs
        its scheduled backward unit (recompute-from-stash + ``jax.vjp``,
        the last chunk seeding the cotangent from the criterion — so
        per-unit activation memory never exceeds the schedule's exact
        stash bound), accumulates gradients and the loss numerator in
        donated scan carries, and ppermutes the activation/cotangent
        hops. After the scan the optimizer update — plain per-stage, or
        the per-stage bucketed reduce-scatter + 1/N update + all-gather
        over the data axis under the sharded-update composition — fires
        exactly ONCE per accumulated step, preserving
        ``set_grad_accumulation``'s contract.
        """
        ax, s, v, lc, m = self.axis, self.s, self.v, self.lc, self.m
        c = self.sched.n_chunks
        W_a, W_c = self.sched.act_window, self.sched.cot_window
        tables = self._tick_tables()
        criterion = self.criterion
        size_avg = getattr(criterion, "size_average", True)
        data_axis, dp = self.data_axis, self.dp
        su_buckets, optim = self.su_buckets, self.optim
        chunk_of = self._chunk_body

        def body(p_loc, mstate, st, key, d_loc, l_loc, epoch):
            from bigdl_tpu.optim.accumulation import split_microbatches
            stage = jax.lax.axis_index(ax)
            # input_transform runs per microbatch, like the
            # accumulation path: the widened batch is never
            # materialized whole
            ds = split_microbatches(d_loc, m)
            ls = split_microbatches(l_loc, m)
            mb_sd = jax.eval_shape(
                (input_transform or (lambda a: a)),
                jax.ShapeDtypeStruct(ds.shape[1:], ds.dtype))
            # the activation stash and cotangent inbox, indexed
            # [chunk_local, mb % window]; zeros are harmless — every
            # read is schedule-gated
            acts = jnp.zeros((v, W_a) + mb_sd.shape, mb_sd.dtype)
            cots = jnp.zeros((v, W_c) + mb_sd.shape, jnp.float32)
            gacc = jax.tree.map(jnp.zeros_like, p_loc)
            fmsg = jnp.zeros(mb_sd.shape, mb_sd.dtype)
            bmsg = jnp.zeros(mb_sd.shape, jnp.float32)
            num0 = jnp.zeros((), jnp.float32)

            def chunk_rows(tree, cl):
                return jax.tree.map(
                    lambda l: jax.lax.dynamic_slice_in_dim(
                        l, cl * lc, lc, 0), tree)

            def tick(carry, xs):
                acts, cots, gacc, num, fmsg, bmsg = carry
                fc, fm, bc, bm, ifc, ifm, ibc, ibm = \
                    (jnp.take(row, stage) for row in xs)
                # 1) deposit last tick's neighbor hops into their slots
                ci, si = jnp.clip(ifc, 0, v - 1), \
                    jnp.clip(ifm, 0, m - 1) % W_a
                acts = acts.at[ci, si].set(
                    jnp.where(ifc >= 0, fmsg.astype(acts.dtype),
                              acts[ci, si]))
                ci, si = jnp.clip(ibc, 0, v - 1), \
                    jnp.clip(ibm, 0, m - 1) % W_c
                cots = cots.at[ci, si].set(
                    jnp.where(ibc >= 0, bmsg, cots[ci, si]))

                # 2) forward unit
                fcl = jnp.clip(fc, 0, v - 1)
                fmb = jnp.clip(fm, 0, m - 1)
                g_glob_f = fcl * s + stage

                def do_fwd(_):
                    x_data = jax.lax.dynamic_index_in_dim(
                        ds, fmb, 0, keepdims=False)
                    if input_transform is not None:
                        x_data = input_transform(x_data)
                    x_in = jnp.where(g_glob_f == 0,
                                     x_data.astype(acts.dtype),
                                     acts[fcl, fmb % W_a])
                    chunk = chunk_of(jax.random.fold_in(key, fmb))
                    y = chunk(chunk_rows(p_loc, fcl), x_in, g_glob_f)
                    return y.astype(acts.dtype), x_in

                def no_fwd(_):
                    z = jnp.zeros(mb_sd.shape, acts.dtype)
                    return z, z

                y_f, x_f = jax.lax.cond(fc >= 0, do_fwd, no_fwd, None)
                # stash the consumed input for the backward recompute
                # (chunk 0's input came from the data, not the inbox)
                acts = acts.at[fcl, fmb % W_a].set(
                    jnp.where(fc >= 0, x_f, acts[fcl, fmb % W_a]))

                # 3) backward unit: recompute-from-stash + vjp; the
                # last chunk seeds its cotangent from the criterion
                bcl = jnp.clip(bc, 0, v - 1)
                bmb = jnp.clip(bm, 0, m - 1)
                g_glob_b = bcl * s + stage

                def do_bwd(_):
                    x_in = acts[bcl, bmb % W_a]
                    chunk = chunk_of(jax.random.fold_in(key, bmb))
                    y, vjp_fn = jax.vjp(
                        lambda pc, xx: chunk(pc, xx, g_glob_b),
                        chunk_rows(p_loc, bcl), x_in)
                    lb = jax.lax.dynamic_index_in_dim(
                        ls, bmb, 0, keepdims=False)
                    lossv, cvjp = jax.vjp(
                        lambda yy: criterion.apply(yy, lb), y)
                    cot_loss = cvjp(jnp.ones_like(lossv))[0]
                    is_last = g_glob_b == c - 1
                    cot_y = jnp.where(is_last,
                                      cot_loss.astype(jnp.float32),
                                      cots[bcl, bmb % W_c])
                    gp, gx = vjp_fn(cot_y.astype(y.dtype))
                    return (gp, gx.astype(jnp.float32),
                            jnp.where(is_last,
                                      lossv.astype(jnp.float32), 0.0))

                def no_bwd(_):
                    return (jax.tree.map(
                        lambda l: jnp.zeros((lc,) + l.shape[1:],
                                            l.dtype), p_loc),
                        jnp.zeros(mb_sd.shape, jnp.float32),
                        jnp.zeros((), jnp.float32))

                gp, gx, lossv = jax.lax.cond(bc >= 0, do_bwd, no_bwd,
                                             None)
                gacc = jax.tree.map(
                    lambda acc, g: jax.lax.dynamic_update_slice_in_dim(
                        acc,
                        jax.lax.dynamic_slice_in_dim(
                            acc, bcl * lc, lc, 0) + g,
                        bcl * lc, 0),
                    gacc, gp)
                num = num + lossv

                # 4) neighbor hops for the next tick
                down = [(i, (i + 1) % s) for i in range(s)]
                up = [(i, (i - 1) % s) for i in range(s)]
                fmsg = jax.lax.ppermute(y_f, ax, down)
                bmsg = jax.lax.ppermute(gx, ax, up)
                return (acts, cots, gacc, num, fmsg, bmsg), None

            init = (acts, cots, gacc, num0, fmsg, bmsg)
            (_, _, grads, num, _, _), _ = jax.lax.scan(tick, init,
                                                       tables)
            # only the device owning the last chunk accumulated loss
            num = jax.lax.psum(num, ax)
            if size_avg:
                num = num / m
                grads = jax.tree.map(lambda g: g / m, grads)
            if data_axis is not None:
                num = jax.lax.pmean(num, data_axis)
            st = dict(st, epoch=epoch)
            if su_buckets is None:
                if data_axis is not None:
                    grads = jax.lax.pmean(grads, data_axis)
                grads = _clip_local(grads, grad_clip, (ax,))
                new_p, new_st = optim.update(grads, p_loc, st)
            else:
                new_p, new_st = _stage_sharded_update(
                    su_buckets, optim, grads, p_loc, st,
                    data_axis=data_axis, n_data=dp, pipe_axis=ax,
                    grad_clip=grad_clip)
            return new_p, mstate, new_st, num

        mesh = self.mesh
        pspec = P(self.axis)
        dspec = P(self.data_axis) if self.data_axis else P()

        def step(params, mstate, opt_state, rng, data, labels, epoch,
                 n_valid=None):
            if n_valid is not None:
                raise ValueError(
                    "pipeline_stages does not compose with "
                    "pad_partial_batches — pad in the dataset pipeline")
            from bigdl_tpu.optim.accumulation import \
                validate_microbatches
            rows = (data.shape[0] // self.dp if self.data_axis
                    else data.shape[0])
            validate_microbatches(rows, m, what="per-shard batch")
            # blocks must map activations shape/dtype-identically —
            # the stash and the neighbor hops are one uniform buffer
            mb_sd0 = jax.ShapeDtypeStruct(
                (rows // m,) + tuple(data.shape[1:]), data.dtype)
            if input_transform is not None:
                mb_sd0 = jax.eval_shape(input_transform, mb_sd0)
            out_sd = jax.eval_shape(
                lambda p, x: self.template.apply(
                    p, self.model.state["0"], x, training=False)[0],
                self.model.params["0"], mb_sd0)
            if (tuple(out_sd.shape) != tuple(mb_sd0.shape)
                    or out_sd.dtype != mb_sd0.dtype):
                raise ValueError(
                    f"pipeline blocks must preserve the activation "
                    f"shape/dtype (got {mb_sd0.shape}/{mb_sd0.dtype} -> "
                    f"{out_sd.shape}/{out_sd.dtype})")
            sspec = self._state_spec(opt_state)
            return shard_map(
                body, mesh=mesh,
                in_specs=(pspec, P(), sspec, P(), dspec, dspec, P()),
                out_specs=(pspec, P(), sspec, P()),
                check_rep=False)(params, mstate, opt_state, rng, data,
                                 labels, epoch)

        return step


def _clip_local(grads, clip, psum_axes) -> dict:
    """Gradient clipping on the stage-local domain: the global L2 norm
    is a ``psum`` of per-stage square sums over the pipe axis (stages
    hold disjoint parameters, so the sum IS the whole-model norm)."""
    if not clip:
        return grads
    if clip["min_value"] is not None:
        grads = jax.tree.map(
            lambda g: jnp.clip(g, clip["min_value"], clip["max_value"]),
            grads)
    if clip["l2_norm"] is not None:
        local = sum(jnp.sum(jnp.square(g))
                    for g in jax.tree.leaves(grads))
        norm = jnp.sqrt(jax.lax.psum(local, psum_axes))
        scale = jnp.minimum(1.0, clip["l2_norm"] / (norm + 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)
    return grads


def _stage_sharded_update(buckets, optim, grads, params, st, *,
                          data_axis, n_data, pipe_axis, grad_clip):
    """The sharded-update composition inside the pipeline body
    (arXiv:2004.13336 per stage): flatten this stage's gradients into
    its reverse-order buckets, ``psum_scatter`` each over the data axis
    (the bucketed reduce-scatter — reverse-topological order within the
    stage is preserved, so earlier buckets' collectives can overlap the
    schedule's remaining backward units), update the 1/N parameter and
    optimizer-state slices, and all-gather the updated parameters."""
    fg = buckets.flatten(grads)
    fp = buckets.flatten(params)
    idx = jax.lax.axis_index(data_axis)
    g_sl, p_sl = {}, {}
    for bk in buckets.keys:
        slen = buckets.padded_sizes[bk] // n_data
        g_sl[bk] = jax.lax.psum_scatter(
            fg[bk], data_axis, scatter_dimension=0, tiled=True) / n_data
        p_sl[bk] = jax.lax.dynamic_slice_in_dim(fp[bk], idx * slen,
                                                slen, 0)
    g_sl = _clip_local(g_sl, grad_clip, (pipe_axis, data_axis))
    su = st.pop("_su", {})
    st_sl = dict(st)
    by_state: dict = {}
    for name, vec in su.items():
        sk, bk = name.rsplit(".", 1)
        by_state.setdefault(sk, {})[bk] = vec
    for sk, bks in by_state.items():
        st_sl[sk] = bks
    new_p_sl, new_st_sl = optim.update(g_sl, p_sl, st_sl)
    new_fp = {bk: jax.lax.all_gather(new_p_sl[bk], data_axis,
                                     tiled=True)
              for bk in buckets.keys}
    new_st = {k: v for k, v in new_st_sl.items()
              if k not in by_state}
    new_su = {}
    for sk in by_state:
        for bk, vec in new_st_sl[sk].items():
            new_su[f"{sk}.{bk}"] = vec
    if new_su or su:
        new_st["_su"] = new_su
    return buckets.unflatten(new_fp), new_st
