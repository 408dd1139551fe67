#!/usr/bin/env python3
"""The builder's proof runs of one cell: ``--sets`` sets of ``--runs``
runs, the same seeds in every set and another seed each run, every run a
new process of the benchmark's own command; then the spreads the bounds
are set from. This process never touches jax (one process per chip).

    python3 benchmarks/tools/proof_runs.py --workload <cell> [--runs 6 --sets 2 --traced 1] --out chiprun_out/proof
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

#: large on purpose: the driver's seeds pass 2**31
SEED_BASE = 2_147_483_600


def one_run(workload, seed, seconds, trace, log_path, extra=()):
    cmd = [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
           "--workload", workload, "--seed", str(seed), "--trace",
           str(trace), *extra]
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.run(cmd, cwd=REPO, stdout=subprocess.PIPE,
                              stderr=log, text=True)
    wall = time.monotonic() - t0
    with open(log_path, "a") as log:
        log.write(proc.stdout)
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    try:
        line = json.loads(lines[-1]) if proc.returncode == 0 else None
    except (IndexError, ValueError):
        line = None
    return {"rc": proc.returncode, "wall_s": wall, "seed": seed,
            "trace": trace, "line": line}


def main(argv=None) -> int:
    from benchmarks.stats import iqr_share
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--traced", type=int, default=1,
                    help="traced runs after the sets")
    ap.add_argument("--out", default="chiprun_out/proof")
    ap.add_argument("--candidate", action="store_true")
    args = ap.parse_args(argv)
    extra = ("--candidate",) if args.candidate else ()
    os.makedirs(args.out, exist_ok=True)
    stem = os.path.join(args.out, args.workload)
    results = []
    for s in range(args.sets):
        for r in range(args.runs):
            got = one_run(args.workload, SEED_BASE + r, args.seconds, 0,
                          f"{stem}.set{s}.run{r}.log", extra)
            got.update(set=s, run=r)
            results.append(got)
            line = got["line"] or {}
            print(f"set {s} run {r} seed {got['seed']} rc {got['rc']} "
                  f"wall {got['wall_s']:.1f}s correct "
                  f"{line.get('correct')} " + json.dumps(
                      {k: round(v["value"], 4) for k, v in
                       line.get("metrics", {}).items()}), flush=True)
    for t in range(args.traced):
        got = one_run(args.workload, SEED_BASE + 100 + t, args.seconds, 1,
                      f"{stem}.traced{t}.log", extra)
        got.update(set="traced", run=t)
        results.append(got)
        print(f"traced {t} rc {got['rc']} wall {got['wall_s']:.1f}s " +
              json.dumps(got["line"])[:6000], flush=True)
    with open(stem + ".jsonl", "w") as f:
        for got in results:
            f.write(json.dumps(got) + "\n")

    names = sorted({k for g in results if g["line"] and g["trace"] == 0
                    for k in g["line"]["metrics"]})
    print(f"\n== {args.workload}: spreads (IQR / median) ==")
    for name in names:
        row, medians = [], []
        for s in range(args.sets):
            vals = [g["line"]["metrics"][name]["value"] for g in results
                    if g["set"] == s and g["line"]
                    and name in g["line"]["metrics"]]
            if name == "setup_s" and s == 0:
                vals = vals[1:]             # the first run compiles
            if len(vals) >= 2:
                medians.append(statistics.median(vals))
                row.append(f"set {s}: median {medians[-1]:.4f} spread "
                           f"{100 * iqr_share(vals):.2f}% (n={len(vals)}, "
                           f"min {min(vals):.4f} max {max(vals):.4f})")
        shift = (f"; second median / first = "
                 f"{medians[1] / medians[0]:.4f}" if len(medians) > 1
                 else "")
        print(f"{name}: " + " | ".join(row) + shift)
    bad = [g for g in results if not g["line"]
           or not g["line"].get("correct")]
    print(f"runs: {len(results)}, not correct or failed: {len(bad)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
