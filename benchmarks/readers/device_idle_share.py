"""1 - (union of device operation intervals / traced window), averaged
over the chips used. %."""


def read(rec, params):
    tw = rec.get("trace_window")
    if not tw or tw["idle_share"] is None:
        return None
    return {"value": 100.0 * tw["idle_share"], "busy_s": tw["busy_s"],
            "window_s": tw["window_s"], "marked": tw["marked"],
            "per_chip_busy_s": tw["per_chip"]}
