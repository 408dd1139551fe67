"""The experts' routing as the program states it: inside a profiler
session ``Optimizer._drain_pending`` reads the routing telemetry of the
module state (``parallel/expert.py``: ``moe_state_stats``) with the
pending losses and writes one ``bigdl:optim:expert_state`` annotation,
``{"step": n, "layers": {path: {stat: float}}}``: the state the
window's LAST step left, so a trace of four steps drained two at a time
holds two samples.

params ``metric``:

- ``product_row_share``: the mean over layers and samples of
  ``moe_product_row_share`` (rows in the held experts' groups over the
  rows of the chunks that ran: the part of a chunk the grouped products
  multiply). %. The note gives the mean ``moe_chunks_run`` and
  ``moe_local_assignment_share``.
- ``held_load_max_over_mean``: ``moe_held_load_max /
  moe_held_load_mean`` (the busiest held expert's assignments over the
  mean one's; 1 is balanced), the mean over the layers and samples in
  which a held expert got a row at all (the others are counted in
  ``without_load``). The note gives, where the router has a selection
  bias, the means of ``moe_load_max_over_mean`` (over ALL experts) and
  ``moe_bias_abs_max``.

Nothing to read where the trace holds no such annotation (a program
without the statement, a model without routing telemetry).
"""
from __future__ import annotations

import json

STATE_EVENT = "bigdl:optim:expert_state"


def samples(events) -> list[dict]:
    """The statements in the trace, by step."""
    out = []
    for e in events:
        label, _, body = e[2].partition(" ")
        if label == STATE_EVENT and body:
            out.append(json.loads(body))
    return sorted(out, key=lambda s: s["step"])


def _mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


def _mean_of(layers, key):
    return _mean(stats[key] for stats in layers if key in stats)


def product_row_share(layers):
    share = _mean_of(layers, "moe_product_row_share")
    if share is None:
        return None
    return {"value": 100.0 * share,
            "chunks_run": _mean_of(layers, "moe_chunks_run"),
            "local_assignment_share":
                _mean_of(layers, "moe_local_assignment_share")}


def held_load_max_over_mean(layers):
    loaded = [s for s in layers if s.get("moe_held_load_mean", 0.0) > 0]
    if not loaded:
        return None
    return {"value": _mean(s["moe_held_load_max"] / s["moe_held_load_mean"]
                           for s in loaded),
            "without_load": len(layers) - len(loaded),
            "load_max_over_mean": _mean_of(layers, "moe_load_max_over_mean"),
            "bias_abs_max": _mean_of(layers, "moe_bias_abs_max")}


METRICS = {"product_row_share": product_row_share,
           "held_load_max_over_mean": held_load_max_over_mean}


def read(rec, params):
    got = samples(rec.get("trace_events") or ())
    layers = [stats for s in got for stats in s["layers"].values()]
    if not layers:
        return None
    note = METRICS[params["metric"]](layers)
    if note is not None:
        note.update(samples=len(got), steps=[s["step"] for s in got],
                    layers=len(got[0]["layers"]))
    return note
