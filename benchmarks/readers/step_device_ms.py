"""Device-busy milliseconds per training step: the union of the device
operation intervals inside the traced window (which begins and ends at
drain points, so it holds whole steps) over the steps traced."""


def read(rec, params):
    tw, steps = rec.get("trace_window"), rec.get("traced_steps")
    if not tw or not steps:
        return None
    return 1e3 * tw["busy_s"] / steps
