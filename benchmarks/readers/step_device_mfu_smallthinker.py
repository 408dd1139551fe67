"""``step_device_mfu`` for SmallThinker: model FLOPs a step per chip
from ``shapes_smallthinker.train_step_flops`` (the chip's share: the
expected local expert assignments, the router over all experts, the
window and global attention cores over the pairs the model needs;
recomputation not counted) over device-busy seconds a step x the chip's
peak. %."""
from benchmarks import shapes_smallthinker
from benchmarks.readers import step_device_ms


def read(rec, params):
    ms = step_device_ms.read(rec, params)
    if ms is None or rec.get("peaks") is None:
        return None
    flops = shapes_smallthinker.train_step_flops(
        rec["loaded"]["config"], rec["global_batch"], rec["seq"])
    per_chip = flops["total"] / rec["chips"]
    value = 100.0 * per_chip / (ms / 1e3 * rec["peaks"]["flops_per_s"])
    return {"value": value, "flops_per_step_per_chip": per_chip,
            **{k: flops[k] for k in ("matmul", "projections", "experts",
                                     "head", "window_core", "global_core")}}
