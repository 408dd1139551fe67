"""From a run's record to the one last line: the end-to-end metrics
(computed HERE, never read from the program), the per-layer metrics
(each by its reader), ``correct``, and the device block."""
from __future__ import annotations

import math

from benchmarks import stats, trace_reduce, tracing
from benchmarks.manifest import plugin


# ---- end-to-end metrics: name -> function of the record ----------------

def _train_records_per_s_per_chip(rec):
    """Whole steps completed between the first and the last loss that
    arrived inside the window, x global batch / elapsed / chips."""
    w = rec["window"]
    elapsed = w["t1"] - w["t0"]
    if elapsed <= 0 or not rec["steps"]:
        return None
    return len(rec["steps"]) * rec["global_batch"] / elapsed / rec["chips"]


def _request_p95_ms(key):
    """95th percentile of a per-request latency over ALL requests due in
    the window, failures ranked slowest (so it can be infinite)."""
    def read(rec):
        p, _ = stats.latency_percentile(
            [r[key] for r in rec["requests"]], 95)
        return None if p is None else p * 1e3
    return read


END_TO_END = {
    "setup_s": lambda rec: rec["setup_s"],
    "train.records_per_s_per_chip": _train_records_per_s_per_chip,
    "serve.ttft_p95_ms": _request_p95_ms("ttft_s"),
    "serve.tpot_p95_ms": _request_p95_ms("tpot_s"),
}


def end_to_end_fn(name: str):
    """A metric the table above lacks is a module of its own under
    ``benchmarks/readers/`` named after it (dots as underscores), so a
    later benchmark PR adds a file and edits none."""
    if name in END_TO_END:
        return END_TO_END[name]
    return plugin("readers", name.replace(".", "_").replace("-", "_")).read


def assemble(rec: dict, *, traced: bool, rehearsal: bool, log) -> dict:
    loaded = rec["loaded"]
    metrics = {}
    tails_finite = True
    if traced:
        rec["trace_window"] = (tracing.reduce_window(rec["trace_events"])
                               if rec.get("trace_events") else None)
        for spec in loaded["per_layer"]:
            reader = plugin("readers", spec["reader"])
            value = reader.read(rec, spec.get("params", {}))
            if isinstance(value, dict):      # value + a note for people
                log(f"  {spec['name']}: {value}")
                value = value.get("value")
            if value is None:
                log(f"  {spec['name']}: nothing to read, left out")
                continue
            metrics[spec["name"]] = {"value": float(value),
                                     "unit": spec["unit"]}
    else:
        for m in loaded["end_to_end"]:
            value = end_to_end_fn(m["name"])(rec)
            if value is None or not math.isfinite(value):
                tails_finite = False
                log(f"  {m['name']}: {value} — no finite value")
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    checks = rec["checks"]
    correct = bool(checks["ok"]) and rec["compiles_in_window"] == 0 \
        and tails_finite
    log(f"checks: {checks}")
    log(f"compilations inside the window: {rec['compiles_in_window']}")
    device = dict(rec["device"],
                  memory_peak_bytes=int(rec["memory_peak_bytes"]))
    line = {"correct": correct, "attempted": int(rec["attempted"]),
            "failed": int(rec["failed"]), "metrics": metrics,
            "device": device}
    if traced:
        tw = rec.get("trace_window")
        if tw is not None:
            device["busy_s"] = tw["busy_s"]
            device["window_s"] = tw["window_s"]
            ev, win = rec["trace_events"], tw["window_ns"]
            line["breakdown"] = {
                "device_ops": trace_reduce.top_ops(ev, win, 10),
                "idle_gaps": trace_reduce.idle_gaps(ev, win, 5)}
        elif not rehearsal:
            line["correct"] = False
            log("traced run found no device plane in the trace")
    if rehearsal:
        line["rehearsal"] = ("REHEARSAL ONLY: tiny widths, not the chip "
                             "- not a result")
    return line
