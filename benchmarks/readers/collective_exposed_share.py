"""Time inside collective operations on a chip during which no other
operation runs on it, over the traced window; the hidden part goes on an
earlier output line. %. Nothing to read on one chip."""
from benchmarks import trace_reduce


def read(rec, params):
    tw = rec.get("trace_window")
    if not tw or rec["chips"] < 2:
        return None
    split = trace_reduce.collective_split(rec["trace_events"],
                                          tw["window_ns"], tw["planes"])
    return {"value": 100.0 * split["exposed_s"] / tw["window_s"],
            "exposed_s": split["exposed_s"], "hidden_s": split["hidden_s"]}
