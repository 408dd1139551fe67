"""Measured autotuning over the stack's performance knobs (ROADMAP 4).

TVM-style search (arXiv:1802.04799) scaled down to the knobs this repo
actually has: Pallas tile configurations (flash attention BQ/BK, fused-CE
token/vocab tiles, LRN spatial tiles, maxpool H/N tiles), batch geometry,
and the sharded-update ``bucket_mb``. The search is MEASURED — each
surviving candidate is compiled and timed on the live backend — but
pruned and ordered first by a static cost model so obviously illegal or
VMEM-overflowing candidates never compile:

- ``est_vmem(config)`` estimates a candidate's peak VMEM footprint;
  anything past ``vmem_budget`` is skipped without building (the menu
  comments in ops/pallas/* record real OOMs at exactly these sizes).
- ``seed_stats`` — the per-executable FLOPs/bytes table
  ``observability.compile_watch.executable_stats`` extracts for the
  incumbent configuration — feeds ``est_cost(config, stats)`` so the
  most promising candidates measure first and ``max_candidates`` cuts
  the tail of a bandwidth-dominated search space instead of a random
  subset.

Winners persist to :mod:`tuning.records` keyed by (kernel, abstract
shape signature, device kind); the kernel block pickers consult that
store before their static menus, so one tuning pass feeds the whole
fleet.

HOST-ONLY CONTRACT (jaxlint JX5): jax is imported lazily inside the
measurement helpers only.
"""
from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field

from bigdl_tpu.tuning.records import TuningRecords, default_records

__all__ = ["tune", "TuneResult", "Measurement", "VMEM_BUDGET_BYTES",
           "flash_candidates", "flash_est_vmem", "fused_ce_candidates",
           "fused_ce_est_vmem", "lrn_candidates", "lrn_est_vmem",
           "maxpool_candidates", "bucket_mb_candidates",
           "batch_geometry_candidates", "chunk_records_candidates",
           "tile_divisors",
           "paged_attention_candidates", "paged_attention_est_vmem",
           "step_memory_candidates", "step_memory_est_hbm",
           "pipeline_schedule_candidates", "pipeline_est_hbm"]

logger = logging.getLogger("bigdl_tpu.tuning")

#: per-core VMEM to budget tile temporaries against (16 MB on v4/v5e;
#: the estimate is deliberately conservative — double-buffered inputs
#: plus f32 scratch)
VMEM_BUDGET_BYTES = 16 * (1 << 20)


@dataclass
class Measurement:
    config: dict
    time_s: float | None          # None when skipped/failed
    skipped: str | None = None    # why it never ran (pruned/error)


@dataclass
class TuneResult:
    config: dict                  # the winner
    time_s: float
    baseline_time_s: float | None
    tie: bool                     # winner == baseline config
    measurements: list[Measurement] = field(default_factory=list)
    record_key: str | None = None


def _sync(out) -> None:
    """Wait for a candidate run to finish on the device: a host read
    of its first output leaf."""
    import jax
    leaves = [l for l in jax.tree.leaves(out)
              if hasattr(l, "dtype") and hasattr(l, "shape")]
    if leaves:
        jax.device_get(leaves[0])


def tune(build, candidates, *, key, records: TuningRecords | None = None,
         warmup: int = 1, iters: int = 3, est_vmem=None,
         vmem_budget: int = VMEM_BUDGET_BYTES, seed_stats: dict | None
         = None, est_cost=None, max_candidates: int | None = None,
         baseline: dict | None = None, persist: bool = True
         ) -> TuneResult:
    """Measured search over ``candidates`` (dicts of knob values).

    ``build(config)`` returns a zero-argument callable running the
    workload once at that configuration (its first call may compile —
    compile time is excluded by the ``warmup`` calls). ``key`` is the
    ``(kernel, signature)`` pair the winner persists under.

    Static pruning happens BEFORE ``build``: candidates whose
    ``est_vmem(config)`` exceeds ``vmem_budget`` are skipped unbuilt,
    and when ``est_cost(config, seed_stats)`` is given the survivors
    measure in ascending predicted-cost order with ``max_candidates``
    bounding the measured set (the cut is logged — never silent). A
    candidate whose build/run raises is recorded as skipped with the
    error, not fatal: an illegal tile is a pruning-model gap, not a
    tuning failure.

    ``baseline`` (e.g. the static-menu pick) is measured alongside; a
    winner that IS the baseline is reported as a tie and logged.
    Returns the :class:`TuneResult`; the winner is persisted to
    ``records`` unless ``persist=False``.
    """
    kernel, sig = key
    cands = [dict(c) for c in candidates]
    if baseline is not None and baseline not in cands:
        cands.append(dict(baseline))
    measurements: list[Measurement] = []
    runnable: list[dict] = []
    for c in cands:
        if est_vmem is not None:
            try:
                need = est_vmem(c)
            except Exception as e:
                measurements.append(Measurement(c, None,
                                                f"est_vmem error: {e}"))
                continue
            if need is not None and need > vmem_budget:
                measurements.append(Measurement(
                    c, None, f"pruned: est VMEM {need / 2**20:.1f} MB > "
                             f"budget {vmem_budget / 2**20:.1f} MB"))
                continue
        runnable.append(c)
    if est_cost is not None:
        runnable.sort(key=lambda c: est_cost(c, seed_stats))
    if max_candidates is not None and len(runnable) > max_candidates:
        cut = runnable[max_candidates:]
        # the baseline must always measure, or the tie/beat verdict
        # would compare against nothing
        keep = runnable[:max_candidates]
        if baseline is not None and baseline in cut:
            keep.append(dict(baseline))
            cut = [c for c in cut if c != baseline]
        logger.info("tune(%s): measuring %d of %d candidates "
                    "(cost-model cut dropped %d: %s)", kernel, len(keep),
                    len(runnable), len(cut), cut)
        for c in cut:
            measurements.append(Measurement(c, None,
                                            "pruned: cost-model cut"))
        runnable = keep

    best: Measurement | None = None
    baseline_time = None
    for c in runnable:
        try:
            fn = build(c)
            for _ in range(max(warmup, 1)):   # first call pays compile
                _sync(fn())
            t0 = time.perf_counter()
            out = None
            for _ in range(max(iters, 1)):
                out = fn()
            _sync(out)
            dt = (time.perf_counter() - t0) / max(iters, 1)
        except Exception as e:
            measurements.append(Measurement(c, None,
                                            f"{type(e).__name__}: {e}"))
            logger.info("tune(%s): candidate %s failed: %s", kernel, c, e)
            continue
        m = Measurement(c, dt)
        measurements.append(m)
        if baseline is not None and c == baseline:
            baseline_time = dt
        if best is None or dt < best.time_s:
            best = m
    if best is None:
        raise ValueError(
            f"tune({kernel}): no candidate survived — "
            f"{[m.skipped for m in measurements]}")

    tie = baseline is not None and best.config == baseline
    if tie:
        logger.info("tune(%s, %s): TIE — measured winner equals the "
                    "static default %s (%.3g s)", kernel, sig,
                    best.config, best.time_s)
    elif baseline_time is not None:
        logger.info("tune(%s, %s): %s (%.3g s) beats static %s "
                    "(%.3g s), %.2fx", kernel, sig, best.config,
                    best.time_s, baseline, baseline_time,
                    baseline_time / max(best.time_s, 1e-12))
    rec_key = None
    if persist:
        store = records if records is not None else default_records()
        rec_key = store.record(kernel, sig, best.config,
                               score=best.time_s,
                               meta={"iters": iters,
                                     "measured": sum(
                                         1 for m in measurements
                                         if m.time_s is not None),
                                     "tie_with_static": tie})
    return TuneResult(config=dict(best.config), time_s=best.time_s,
                      baseline_time_s=baseline_time, tie=tie,
                      measurements=measurements, record_key=rec_key)


# ---------------------------------------------------------------------------
# candidate generators + VMEM estimators, one pair per tuned kernel.
# The estimators model the f32 scratch + double-buffered block inputs of
# the actual kernels (ops/pallas/*) — deliberately a slight OVERestimate
# so the prune errs toward skipping a config that might have fit.
# ---------------------------------------------------------------------------

def tile_divisors(n: int, cap: int, floor: int = 128, step: int = 16
                  ) -> list[int]:
    """Tile sizes that legally divide ``n``: multiples of ``step`` (the
    bf16 sublane tile — legal for f32 too) from ``cap`` down to
    ``floor``, largest first."""
    top = min(cap, n)
    return [b for b in range(top - top % step, floor - 1, -step)
            if n % b == 0]


def flash_candidates(sq: int, skv: int, *, q_cap: int = 512,
                     k_cap: int = 1024) -> list[dict]:
    """(BQ, BK) grid for flash attention at (sq, skv): every legal
    divisor pair, menu sizes included."""
    return [{"bq": bq, "bk": bk}
            for bq in tile_divisors(sq, q_cap)
            for bk in tile_divisors(skv, k_cap)]


def flash_est_vmem(d: int, dtype_bytes: int = 2, sq: int | None = None):
    """Footprint at head dim ``d`` of the fattest kernel a (bq, bk) pair
    runs, the backward: four f32 tiles (s, p, dp, ds), the f32 dq/dk/dv
    accumulators, double-buffered q/dO/k/v blocks, lanes padded to 128.
    ``sq``: causal self-attention of that length — the looped kernels
    then keep a head in VMEM (K, V and the dk/dv outputs double-buffered,
    the f32 dk/dv scratch) beside a tile of bq x bk, counted as
    ``ops/pallas/flash_attention._schedule`` counts it and dropped where
    it exceeds that module's budget: the kernels stream then."""
    lanes = -(-d // 128) * 128
    resident = 0
    if sq is not None:
        from bigdl_tpu.ops.pallas.flash_attention import _RESIDENT_BUDGET
        resident = 4 * 2 * sq * lanes * dtype_bytes + 2 * sq * lanes * 4
        if resident > _RESIDENT_BUDGET:
            resident = 0

    def est(c: dict) -> int:
        bq, bk = c["bq"], c["bk"]
        f32 = 4
        return (4 * bq * bk * f32 + (bq + 2 * bk) * lanes * f32
                + 2 * 2 * (bq + bk) * lanes * dtype_bytes + resident)
    return est


def fused_ce_candidates(n: int, v: int, *, t_cap: int = 512,
                        v_cap: int = 1024) -> list[dict]:
    return [{"bt": bt, "bv": bv}
            for bt in tile_divisors(n, t_cap)
            for bv in tile_divisors(v, v_cap)]


def fused_ce_est_vmem(d: int, dtype_bytes: int = 2):
    """dW-kernel footprint (the fattest of the three): f32 logits tile
    (bt, bv), f32 dw scratch (bv, d), double-buffered h/w blocks."""
    def est(c: dict) -> int:
        bt, bv = c["bt"], c["bv"]
        f32 = 4
        return (bt * bv * f32 + bv * d * f32
                + 2 * (bt * d + bv * d) * dtype_bytes)
    return est


def lrn_candidates(hw: int) -> list[dict]:
    """Spatial-row tiles for the LRN kernel: powers of two dividing the
    plane (the swept menu was 1..16; >=16 OOMed at C=192, N=256 — the
    estimator prunes those shapes per-geometry instead of globally)."""
    return [{"ht": ht} for ht in (16, 8, 4, 2, 1) if hw % ht == 0]


def lrn_est_vmem(c_dim: int, n: int):
    def est(c: dict) -> int:
        # ~4 live f32 (ht, C, N) temps in the backward (x, s, acc, dx)
        return 4 * c["ht"] * c_dim * n * 4
    return est


def maxpool_candidates(h: int, n: int) -> list[dict]:
    """H-tile / N-tile grid for the maxpool backward kernel."""
    hts = [ht for ht in (8, 4, 2) if h % ht == 0] or [h]
    nts = [nt for nt in (256, 128) if n % nt == 0] or [min(n, 256)]
    return [{"h_t": ht, "n_t": nt} for ht in hts for nt in nts]


def paged_attention_candidates(t: int, g: int, *, bt_cap: int = 8,
                               gp_octaves: int = 2) -> list[dict]:
    """(bt, gp) grid for the paged-attention decode kernel at query
    width ``t`` and group size ``g`` (query heads per kv head): every
    divisor of ``t`` up to ``bt_cap`` crossed with sublane-aligned
    group paddings — more padded rows fatten the score tile (MXU
    utilization at tiny G) at the cost of wasted lanes."""
    bts = [b for b in range(min(bt_cap, t), 0, -1) if t % b == 0]
    gp0 = -(-g // 8) * 8
    gps = [gp0 * (1 << k) for k in range(max(gp_octaves, 1))]
    return [{"bt": bt, "gp": gp} for bt in bts for gp in gps]


def paged_attention_est_vmem(s: int, d: int, dtype_bytes: int = 2):
    """Kernel footprint at page size ``s``, head dim ``d``: f32 score +
    prob tiles (R, S), f32 acc (R, D) + m/l columns, double-buffered
    k/v page blocks and the q block (R = bt * gp rows)."""
    def est(c: dict) -> int:
        r = c["bt"] * c["gp"]
        f32 = 4
        return (2 * r * s * f32 + r * (d + 2) * f32
                + 2 * (2 * s * d + r * d) * dtype_bytes)
    return est


def step_memory_candidates(batch: int, *, policies=None,
                           max_microbatches: int = 8) -> list[dict]:
    """``(remat_policy, num_microbatches)`` grid for the train step's
    memory-for-throughput knobs (optim/remat.py, optim/accumulation.py):
    every known policy crossed with the powers of two dividing ``batch``
    up to ``max_microbatches``. The measured ``tune()`` over these picks
    the fastest step that FITS — more microbatches / heavier remat free
    HBM for a larger per-chip batch at the cost of recompute and scan
    overhead."""
    from bigdl_tpu.optim.remat import known_remat_policies
    if policies is None:
        policies = known_remat_policies()
    ks, k = [], 1
    while k <= min(int(max_microbatches), int(batch)):
        if batch % k == 0:
            ks.append(k)
        k *= 2
    return [{"remat_policy": p, "num_microbatches": k}
            for p in policies for k in ks]


def step_memory_est_hbm(residual_bytes_by_policy: dict,
                        persistent_bytes: int = 0):
    """Static peak-HBM estimator for ``step_memory_candidates``
    configs, from per-policy ``saved_residual_bytes`` measured once at
    k=1 (optim/remat.py): the activation term scales with microbatch
    size (1/k), the persistent term (params/grads/optimizer state) does
    not. Use as ``est_vmem=`` with an HBM budget, or as ``est_cost=``
    to order candidates memory-first."""
    def est(c: dict) -> int:
        rb = residual_bytes_by_policy[c["remat_policy"]]
        return int(persistent_bytes + rb // max(int(
            c.get("num_microbatches", 1)), 1))
    return est


def pipeline_schedule_candidates(batch: int, n_layers: int,
                                 stage_counts=(2, 4), *,
                                 max_microbatches: int = 16,
                                 max_virtual: int = 4) -> list[dict]:
    """``(schedule, num_microbatches, stages, virtual_stages)`` grid for
    the pipelined train step (parallel/pipeline.py): every power-of-two
    microbatch count dividing ``batch`` crossed with the stage counts
    that divide the layer stack, gpipe/1f1b at v=1 plus interleaved
    variants while the chunking stays legal (layers divide S*v,
    microbatches divide S). The measured ``tune()`` over these picks the
    schedule with the smallest real step time; the static estimator
    (:func:`pipeline_est_hbm`) prunes configurations whose activation
    stash cannot fit before anything compiles."""
    out = []
    ms, k = [], 1
    while k <= min(int(max_microbatches), int(batch)):
        if batch % k == 0:
            ms.append(k)
        k *= 2
    for s in stage_counts:
        s = int(s)
        if s < 1 or n_layers % s:
            continue
        for m in ms:
            for sched in ("gpipe", "1f1b"):
                out.append({"schedule": sched, "num_microbatches": m,
                            "stages": s, "virtual_stages": 1})
            v = 2
            while v <= int(max_virtual) and n_layers % (s * v) == 0:
                if m % s == 0:
                    out.append({"schedule": "interleaved_1f1b",
                                "num_microbatches": m, "stages": s,
                                "virtual_stages": v})
                v *= 2
    return out


def pipeline_est_hbm(act_bytes_full_batch: int,
                     persistent_bytes: int = 0):
    """Static per-stage HBM estimator for
    :func:`pipeline_schedule_candidates` configs, built on the existing
    per-stage residual model: the schedule's EXACT activation-stash
    bound (``pipeline_schedule_stats`` — M microbatches for gpipe, ~S
    for 1f1b) times the per-microbatch activation bytes
    (``act_bytes_full_batch`` / M — the k=1 ``saved_residual_bytes``
    term scaled the same way ``step_memory_est_hbm`` scales it), plus
    the per-stage share of the persistent bytes. Use as ``est_vmem=``
    with an HBM budget, or as ``est_cost=`` to order candidates
    memory-first."""
    def est(c: dict) -> int:
        from bigdl_tpu.parallel.pipeline import pipeline_schedule_stats
        m = max(int(c.get("num_microbatches", 1)), 1)
        s = max(int(c.get("stages", 1)), 1)
        st = pipeline_schedule_stats(
            m, s, c.get("schedule", "1f1b"),
            virtual_stages=int(c.get("virtual_stages", 1)))
        per_mb = act_bytes_full_batch // m
        return int(persistent_bytes // s
                   + st["peak_stash_microbatches"] * per_mb)
    return est


def bucket_mb_candidates() -> list[dict]:
    """Sharded-update gradient bucket sizes (optim/sharded_update.py):
    small buckets overlap more of the backward, big buckets amortize
    collective latency — the right point is model- and mesh-dependent."""
    return [{"bucket_mb": mb} for mb in (1.0, 2.0, 4.0, 8.0, 16.0)]


def batch_geometry_candidates(global_batch: int, n_shards: int,
                              *, span: int = 2) -> list[dict]:
    """Per-step batch geometries near ``global_batch`` that keep the
    data-axis divisibility contract: halving/doubling within ``span``
    octaves, shard-divisible only."""
    out = []
    for k in range(-span, span + 1):
        b = int(global_batch * (2.0 ** k))
        if b >= n_shards and b % n_shards == 0:
            out.append({"batch": b})
    return out


def chunk_records_candidates(n_records: int,
                             num_shards: int = 1) -> list[dict]:
    """Record-store chunk sizes (dataset/recordstore.py): small chunks
    shuffle finer and rebalance better across hosts, big chunks amortize
    footer/index overhead and read sequentially. Octave scan filtered so
    every shard owns at least one chunk per pass
    (dataset/distributed.py's assignment precondition)."""
    out = []
    for cr in (64, 128, 256, 512, 1024, 2048):
        n_chunks = (int(n_records) + cr - 1) // cr
        if n_chunks >= max(1, int(num_shards)):
            out.append({"chunk_records": cr})
    return out
