"""Device milliseconds a step of the operations that belong to one part
of the step program, the part given by the program's own scopes
(``jax.named_scope`` in ``optim/accumulation.py`` and ``nn/containers.
py``): ``jvp(model)/block_3/...`` is forward, ``transpose(jvp(model))``
backward, ``optimizer_update`` the update.

The v5e's trace names a device operation by its HLO text and carries no
scope, so the program writes the instruction -> scope table of each step
it compiled into the trace as one ``bigdl:compile:step_scopes``
annotation (``observability/tracing.py``), and this reader joins on it:
an operation inside a run of that program (``XLA Modules`` line) is
found in the table by its instruction name. Collectives are left out
(``collective.exposed_share`` has them). Per chip average.

A fusion has ONE scope, its root's: where XLA fuses a weight's AdamW
update into that weight's gradient matmul (one chip) the whole fusion
reads as backward; where an all-reduce stands between the two (data
parallel) the update is a fusion of its own. The table's ``inside``
names the scopes an operation holds besides its root's, so that the
first case can be counted too (``step.update_fused_ms``).

params: ``include`` (regex over the scope; required), ``exclude``
(regex, optional) and ``inside`` (regex, optional: count an operation
only if a scope it holds besides its own matches). Nothing to read where
the trace holds no table.
"""
from __future__ import annotations

import bisect
import json
import re

from benchmarks import trace_reduce

SCOPES_EVENT = "bigdl:compile:step_scopes"


def scope_tables(events) -> dict:
    """``{program name: {instruction name: (scope, (scopes inside))}}``
    from the program's annotations in the trace."""
    tables: dict[str, dict] = {}
    for e in events:
        label, _, body = e[2].partition(" ")
        if label != SCOPES_EVENT or not body:
            continue
        table = json.loads(body)
        held: dict[str, list] = {}
        for scope, names in table.get("inside", {}).items():
            for name in names:
                held.setdefault(name, []).append(scope)
        by_name = tables.setdefault(table["program"], {})
        for scope, names in table["scopes"].items():
            for name in names:
                by_name[name] = (scope, tuple(held.get(name, ())))
    return tables


def step_ops(events, window, planes) -> list[list]:
    """Per device plane, the operations inside whole-or-clipped runs of
    the programs that have a scope table:
    ``[(instruction, scope or None, scopes inside, is_collective,
    seconds)]``, seconds clipped to the window."""
    tables = scope_tables(events)
    out = []
    for plane in planes:
        runs = sorted((s, e, tables[n.split("(", 1)[0]])
                      for n, s, e in trace_reduce.op_events(
                          events, plane, trace_reduce.MODULES_LINE)
                      if n.split("(", 1)[0] in tables)
        starts = [r[0] for r in runs]
        ops = []
        for name, s, e in trace_reduce.op_events(events, plane):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= runs[i][1]:
                continue                       # another program's
            got = trace_reduce.clip([(s, e)], window)
            if not got:
                continue
            instr = name.split(" ", 1)[0].lstrip("%")
            ops.append((instr, *runs[i][2].get(instr, (None, ())),
                        bool(trace_reduce.COLLECTIVE.match(instr)),
                        trace_reduce.length(got) / 1e9))
        out.append(ops)
    return out


def traced_step_ops(rec):
    """:func:`step_ops` of a record, or None where there is nothing to
    read: no traced window, no steps, no scope table in the trace."""
    tw, steps = rec.get("trace_window"), rec.get("traced_steps")
    if not tw or not steps:
        return None
    per_plane = step_ops(rec["trace_events"], tw["window_ns"],
                         tw["planes"])
    return per_plane if any(per_plane) else None


def read(rec, params):
    per_plane = traced_step_ops(rec)
    if per_plane is None:
        return None
    include = re.compile(params["include"])
    exclude, inside = (re.compile(params[k]) if params.get(k) else None
                       for k in ("exclude", "inside"))
    seconds, count = 0.0, 0
    for ops in per_plane:
        for _, scope, held, collective, secs in ops:
            if collective or scope is None or not include.search(scope) \
                    or (exclude is not None and exclude.search(scope)) \
                    or (inside is not None
                        and not any(map(inside.search, held))):
                continue
            seconds += secs
            count += 1
    n = len(per_plane) * rec["traced_steps"]
    return {"value": 1e3 * seconds / n, "ops_per_step": count / n}
