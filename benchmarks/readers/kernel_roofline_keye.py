"""``kernel_roofline`` with one more cost: what the selected attention
needs over the traced steps (``shapes_keye``). The arithmetic is
``kernel_roofline.read`` itself; this file only adds the cost function
to the table that reader looks its ``cost`` up in.

params: ``kernels`` (regex naming EVERY kernel that computes the
selected attention) and ``cost`` = ``sparse_attention_train``.
"""
from benchmarks import shapes_keye
from benchmarks.readers import kernel_roofline


def _sparse_attention_train(rec, tw):
    """Needs of the selected attention over the traced steps, per chip."""
    cost = shapes_keye.sparse_attention_train_cost(
        rec["loaded"]["config"], rec["global_batch"] // rec["chips"],
        rec["seq"])
    steps = rec["traced_steps"]
    return {"flops": cost["flops"] * steps, "bytes": cost["bytes"] * steps}


kernel_roofline.COSTS["sparse_attention_train"] = _sparse_attention_train
read = kernel_roofline.read
