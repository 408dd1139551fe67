"""``kernel_roofline`` with one more cost: what the latent attention's
core needs over the traced steps (``shapes_kimi``). The arithmetic is
``kernel_roofline.read`` itself; this file only adds the cost function
to the table that reader looks its ``cost`` up in.

params: ``kernels`` (regex naming EVERY kernel that computes the core)
and ``cost`` = ``mla_attention_train``.
"""
from benchmarks import shapes_kimi
from benchmarks.readers import kernel_roofline


def _mla_attention_train(rec, tw):
    """Needs of the latent attention's core over the traced steps, per
    chip."""
    cost = shapes_kimi.mla_attention_train_cost(
        rec["loaded"]["config"], rec["global_batch"] // rec["chips"],
        rec["seq"])
    steps = rec["traced_steps"]
    return {"flops": cost["flops"] * steps, "bytes": cost["bytes"] * steps}


kernel_roofline.COSTS["mla_attention_train"] = _mla_attention_train
read = kernel_roofline.read
