"""``step_device_mfu`` for the Kimi family: model FLOPs a step per chip
from ``shapes_kimi.train_step_flops`` (the chip's share: expected local
expert assignments, the shared expert whole, the attention core over
the causal pairs; recomputation not counted) over device-busy seconds a
step x the chip's peak. %."""
from benchmarks import shapes_kimi
from benchmarks.readers import step_device_ms


def read(rec, params):
    ms = step_device_ms.read(rec, params)
    if ms is None or rec.get("peaks") is None:
        return None
    flops = shapes_kimi.train_step_flops(
        rec["loaded"]["config"], rec["global_batch"], rec["seq"])
    per_chip = flops["total"] / rec["chips"]
    value = 100.0 * per_chip / (ms / 1e3 * rec["peaks"]["flops_per_s"])
    return {"value": value, "flops_per_step_per_chip": per_chip,
            **{k: flops[k] for k in ("matmul", "experts", "shared", "head",
                                     "attention")}}
