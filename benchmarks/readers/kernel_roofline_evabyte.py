"""``kernel_roofline`` with one more cost: what EVA attention's step 3
needs over the traced steps (``shapes_evabyte``). The arithmetic is
``kernel_roofline.read`` itself; this file only adds the cost function
to the table that reader looks its ``cost`` up in.

params: ``kernels`` (regex naming EVERY kernel that computes step 3) and
``cost`` = ``eva_attention_train``.
"""
from benchmarks import shapes_evabyte
from benchmarks.readers import kernel_roofline


def _eva_attention_train(rec, tw):
    """Needs of step 3 over the traced steps, per chip."""
    cost = shapes_evabyte.eva_attention_train_cost(
        rec["loaded"]["config"], rec["global_batch"] // rec["chips"],
        rec["seq"])
    steps = rec["traced_steps"]
    return {"flops": cost["flops"] * steps, "bytes": cost["bytes"] * steps}


kernel_roofline.COSTS["eva_attention_train"] = _eva_attention_train
read = kernel_roofline.read
