"""``kernel_roofline`` with two more costs, over the traced steps
(``shapes_smallthinker``): what the WINDOW layers' attention cores need
(seven products over the windowed pairs), and what the GLOBAL layers'
need (over the causal pairs). The arithmetic is ``kernel_roofline.read``
itself; this file only adds the cost functions to the table that reader
looks its ``cost`` up in.

params: ``kernels`` (regex naming EVERY kernel that computes the part:
``flash_window_*`` for the window layers, ``flash_attention_*`` for the
global ones) and ``cost`` = ``window_attention_smallthinker`` or
``flash_attention_smallthinker``.
"""
from benchmarks import shapes_smallthinker
from benchmarks.readers import kernel_roofline


def _cost(kind):
    def cost(rec, tw):
        """Needs of the ``kind`` layers' cores over the traced steps,
        per chip."""
        got = shapes_smallthinker.core_cost(
            rec["loaded"]["config"], rec["global_batch"] // rec["chips"],
            rec["seq"], kind)
        steps = rec["traced_steps"]
        return {"flops": got["flops"] * steps, "bytes": got["bytes"] * steps}
    return cost


kernel_roofline.COSTS["window_attention_smallthinker"] = _cost("window")
kernel_roofline.COSTS["flash_attention_smallthinker"] = _cost("global")
read = kernel_roofline.read
