"""What the compiled step holds on a device, as the compiler counted it
and the program states it: the ``"memory"`` of the
``bigdl:compile:step_scopes`` table (``observability/tracing.py``
``ProgramScopes``: ``compile_watch.memory_stats``' ``arg_bytes``,
``output_bytes``, ``alias_bytes``, ``temp_bytes``, ``code_bytes`` and
``peak_hbm_bytes`` = arguments + outputs + temporaries - aliased; one
device's, under a mesh). ``memory_peak_bytes`` (the runtime's
``peak_bytes_in_use``) does not count a step's temporaries; this does.

The value is ``peak_hbm_bytes`` of the program whose runs (``XLA
Modules`` line of the first device) take most of the traced window, in
GB (1e9 bytes). The note gives the parts and the room left under the
peaks table's ``hbm_bytes`` (``benchmarks/peaks.py``: the published 16
GB; the runtime's own limit on the chip is higher, 16.9 GB). Nothing to
read where the trace holds no table, the table no ``memory`` (a program
without the statement) or the memory is ``null`` (an executable that
gives no analysis): never 0.
"""
from __future__ import annotations

import json

from benchmarks import trace_reduce
from benchmarks.readers.scope_device_ms import SCOPES_EVENT


def step_memories(events) -> dict:
    """``{program name: its "memory" (a dict, or None)}`` from the
    program's annotations in the trace."""
    out = {}
    for e in events:
        label, _, body = e[2].partition(" ")
        if label == SCOPES_EVENT and body:
            table = json.loads(body)
            out[table["program"]] = table.get("memory")
    return out


def read(rec, params):
    tw = rec.get("trace_window")
    if not tw or not rec.get("trace_events"):
        return None
    events, window = rec["trace_events"], tw["window_ns"]
    memories = step_memories(events)
    seconds: dict[str, float] = {}
    for name, s, e in trace_reduce.op_events(events, tw["planes"][0],
                                             trace_reduce.MODULES_LINE):
        program = name.split("(", 1)[0]
        if program in memories:
            seconds[program] = seconds.get(program, 0.0) \
                + trace_reduce.length(trace_reduce.clip([(s, e)], window))
    if not seconds:
        return None
    program = max(seconds, key=seconds.get)
    memory = memories[program]
    if not memory or memory.get("peak_hbm_bytes") is None:
        return None
    note = {"value": memory["peak_hbm_bytes"] / 1e9, "program": program}
    note.update({key.removesuffix("_bytes") + "_gb": memory[key] / 1e9
                 for key in ("arg_bytes", "output_bytes", "temp_bytes",
                             "alias_bytes", "code_bytes") if key in memory})
    if rec.get("peaks"):
        note["room_gb"] = (rec["peaks"]["hbm_bytes"]
                           - memory["peak_hbm_bytes"]) / 1e9
    return note
