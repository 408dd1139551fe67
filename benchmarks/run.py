#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: asserts the platform is a TPU with at least the
cell's chips (no CPU fallback), loads, warms up, measures for
``--seconds``, checks the outputs against the plain reference, prints
earlier lines for people and ONE JSON object as the last line, exits.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. ``--rehearsal`` runs tiny widths on
whatever backend is there, says so on its last line and is never a
result.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _rehearsal_overrides(loaded: dict) -> None:
    """Tiny widths for the CPU: each data file carries its own under
    ``rehearsal``; nothing here knows a cell."""
    for part in ("config", "traffic", "cell"):
        over = loaded[part].get("rehearsal")
        if over:
            loaded[part] = dict(loaded[part], **over)


def make_ctx(loaded: dict, devices, *, seed: int, seconds: float,
             trace: bool = False, rehearsal: bool = False,
             keep_trace=None):
    """What a driver kind is handed: the cell's data, the run's
    arguments, the devices, the compile counter and the peaks."""
    from benchmarks import model_setup, peaks
    from benchmarks.compiles import CompileCounter
    return types.SimpleNamespace(
        name=loaded["name"], cell=loaded["cell"], config=loaded["config"],
        traffic=loaded["traffic"], seed=int(seed), seconds=float(seconds),
        trace=bool(trace), rehearsal=rehearsal, devices=devices,
        t_process_start=T_PROCESS_START, compiles=CompileCounter(),
        keep_trace=keep_trace, log=model_setup.log,
        peaks=(None if rehearsal
               else peaks.peaks_for(devices[0].device_kind)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny widths on any backend; never a result")
    ap.add_argument("--candidate", action="store_true",
                    help="the workload is under benchmarks/candidates/, "
                         "not in BENCHMARK.json yet")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="also copy the raw .xplane.pb of a traced run "
                         "into DIR (debugging)")
    args = ap.parse_args(argv)

    from benchmarks import manifest, model_setup
    man = manifest.load_manifest()
    if args.candidate:
        man = manifest.with_candidate(man, args.workload)
    loaded = manifest.load_cell(args.workload, man)
    if args.rehearsal:
        _rehearsal_overrides(loaded)
    seconds = float(man["run_seconds"] if args.seconds is None
                    else args.seconds)

    # a CPU rehearsal must not start depending on a warm cache
    cache_dir = None if args.rehearsal else model_setup.configure_cache()
    try:
        devices = model_setup.pick_devices(loaded["chips"], args.rehearsal)
    except model_setup.NoAccelerator as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 2
    import bigdl_tpu  # noqa: F401  (the system under test)

    from benchmarks import result
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    model_setup.log(f"device: {device}; compile cache: {cache_dir}")
    ctx = make_ctx(loaded, devices, seed=args.seed, seconds=seconds,
                   trace=args.trace, rehearsal=args.rehearsal,
                   keep_trace=args.keep_trace)

    record = manifest.plugin("kinds", loaded["kind"]).run(ctx)
    record.update(loaded=loaded, device=device, peaks=ctx.peaks,
                  seconds=seconds, seed=ctx.seed,
                  setup_s=record["window"]["t0"] - T_PROCESS_START,
                  compiles_in_window=ctx.compiles.in_window)
    line = result.assemble(record, traced=ctx.trace,
                           rehearsal=args.rehearsal, log=model_setup.log)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
