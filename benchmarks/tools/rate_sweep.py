#!/usr/bin/env python3
"""Find the knee of a serving cell ONCE, on the chip: build the replica
as the cell does, then offer the cell's traffic at each of a few fixed
rates for a short window and print what happened. The result goes into
the traffic file as a number (``rate_rps`` = 0.8 x knee); the benchmark
itself never searches.

    python3 benchmarks/tools/rate_sweep.py --workload <cell> --rates 2,4,6 --seconds 20
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from benchmarks import manifest, model_setup, stats
    man = manifest.load_manifest()
    if not any(w["name"] == args.workload for w in man["workloads"]):
        man = manifest.with_candidate(man, args.workload)
    loaded = manifest.load_cell(args.workload, man)
    model_setup.configure_cache()
    devices = model_setup.pick_devices(loaded["chips"], False)
    import bigdl_tpu  # noqa: F401

    from benchmarks import run
    from benchmarks.kinds import serve
    ctx = run.make_ctx(loaded, devices, seed=args.seed,
                       seconds=args.seconds)
    system = serve.build(ctx)
    rows = []
    try:
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            ctx.traffic = dict(loaded["traffic"], rate_rps=rate)
            ctx.seed = args.seed + k
            rec = serve.measure(ctx, system, id_base=(k + 1) * 1_000_000)
            reqs = rec["requests"]
            half = len(reqs) // 2

            def p(vals, q):
                v, _ = stats.latency_percentile(vals, q)
                return None if v is None else round(v * 1e3, 1)

            done = [r for r in reqs if r["ok"]]
            row = {
                "rate_rps": rate, "due": len(reqs), "finished": len(done),
                "ttft_p50_ms": p([r["ttft_s"] for r in reqs], 50),
                "ttft_p95_ms": p([r["ttft_s"] for r in reqs], 95),
                "tpot_p50_ms": p([r["tpot_s"] for r in reqs], 50),
                "tpot_p95_ms": p([r["tpot_s"] for r in reqs], 95),
                "queue_wait_p50_first_half_ms": p(
                    [r["queue_wait_s"] for r in reqs[:half]], 50),
                "queue_wait_p50_second_half_ms": p(
                    [r["queue_wait_s"] for r in reqs[half:]], 50),
                "lateness_p95_ms": p([r["sent_s"] - r["due_s"]
                                      for r in reqs], 95),
                "last_finish_after_window_s": round(
                    rec["t_end"] - rec["window"]["t1"], 2),
                "completed_tokens_per_s": round(
                    len(done) * int(ctx.traffic["max_new_tokens"])
                    / (rec["t_end"] - rec["window"]["t0"]), 1),
                "compiles_in_window": ctx.compiles.in_window,
            }
            rows.append(row)
            print("SWEEP " + json.dumps(row), flush=True)
            time.sleep(1.0)
    finally:
        system.close()
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
