"""Of the device time of the non-collective operations inside the step
program's runs, the share whose operation carries none of the program's
scopes: guards the instrumentation (a refactor that drops a
``jax.named_scope``, a compiler that stops carrying ``op_name``). %.

params: ``scopes`` (regex: an operation is scoped when its scope matches).
The note lists the unscoped operations with the most time.
"""
from __future__ import annotations

import re

from benchmarks.readers import scope_device_ms


def read(rec, params):
    per_plane = scope_device_ms.traced_step_ops(rec)
    if per_plane is None:
        return None
    scoped = re.compile(params["scopes"])
    total, bare = 0.0, {}
    for ops in per_plane:
        for instr, scope, _, collective, secs in ops:
            if collective:
                continue
            total += secs
            if scope is None or not scoped.search(scope):
                key = re.sub(r"\.\d+$", "", instr)
                bare[key] = bare.get(key, 0.0) + secs
    if total <= 0:
        return None
    n = len(per_plane) * rec["traced_steps"]
    top = sorted(bare.items(), key=lambda kv: -kv[1])[:5]
    return {"value": 100.0 * sum(bare.values()) / total,
            "step_ops_ms": 1e3 * total / n,
            "unscoped_ms": 1e3 * sum(bare.values()) / n,
            "top_unscoped_ms": [[k, 1e3 * v / n] for k, v in top]}
