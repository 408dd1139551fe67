"""A kernel's share of its roofline: the least time the chip could take
for what the kernel's calls need — max(FLOPs / peak FLOP/s, bytes / peak
bytes/s), from the shape functions in shapes.py — over the summed device
time of the kernel's events in the traced window. Says which bound. %.

params: ``kernels`` (regex over the trace's operation names) and ``cost``
(the name of a cost function below).
"""
from benchmarks import shapes, trace_reduce


def _flash_attention_train(rec, tw):
    """Needs of the flash kernels over the traced steps, per chip."""
    cost = shapes.flash_attention_train_cost(
        rec["loaded"]["config"], rec["global_batch"] // rec["chips"],
        rec["seq"])
    steps = rec["traced_steps"]
    return {"flops": cost["flops"] * steps, "bytes": cost["bytes"] * steps}


def _paged_attention_decode(rec, tw):
    """Needs of ``paged_attention`` in the decode bursts that ran inside
    the traced window: every live page read once per decode step."""
    bursts = [b for b in rec.get("bursts", [])
              if b["t0"] >= rec["trace_clock"][0]
              and b["t1"] <= rec["trace_clock"][1]]
    if not bursts:
        return None
    cfg, page = rec["loaded"]["config"], rec["page_size"]
    flops = bytes_ = 0.0
    for b in bursts:
        for ctx0 in b["contexts"]:            # context at the first step
            for j in range(b["steps"]):
                live = -(-(ctx0 + j + 1) // page) * page
                c = shapes.paged_attention_decode_cost(cfg, live, 1)
                flops += c["flops"]
                bytes_ += c["bytes"]
    return {"flops": flops, "bytes": bytes_, "bursts": len(bursts)}


COSTS = {"flash_attention_train": _flash_attention_train,
         "paged_attention_decode": _paged_attention_decode}


def read(rec, params):
    tw = rec.get("trace_window")
    if not tw or rec.get("peaks") is None:
        return None
    cost = COSTS[params["cost"]](rec, tw)
    if cost is None:
        return None
    events, window = rec["trace_events"], tw["window_ns"]
    within = params.get("within_module")
    if within:
        # only the kernel's events inside runs of the named program
        # (e.g. the decode step), and only whole runs
        got = trace_reduce.kernel_seconds_within(
            events, params["kernels"], within, window, tw["planes"])
        if not got["runs"] or not cost.get("bursts"):
            return None
        # per-run averages: the trace's whole runs and the recorder's
        # whole bursts can differ by one at the window's edges
        seconds = got["seconds"] / got["runs"]
        cost = {"flops": cost["flops"] / cost["bursts"],
                "bytes": cost["bytes"] / cost["bursts"]}
        calls = got["calls"]
    else:
        got = trace_reduce.kernel_seconds(events, params["kernels"],
                                          window, tw["planes"])
        seconds, calls = got["seconds"], got["calls"]
    if seconds <= 0:
        return None
    least, bound = shapes.roofline_least_seconds(cost, rec["peaks"])
    return {"value": 100.0 * least / seconds, "bound": bound,
            "kernel_s": seconds, "least_s": least, "calls": calls}
