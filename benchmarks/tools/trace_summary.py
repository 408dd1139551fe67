#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, event counts, the names
with the most time on each line. Optionally cut a slice of the events
into a small ``.jsonl.gz`` of plain tuples (a test fixture).

    python3 benchmarks/tools/trace_summary.py <dir-or-xplane.pb> [--cut OUT.jsonl.gz --from-ms A --to-ms B]
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    from benchmarks import xplane
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--cut", default=None)
    ap.add_argument("--from-ms", type=float, default=0.0)
    ap.add_argument("--to-ms", type=float, default=float("inf"))
    ap.add_argument("--name-width", type=int, default=160,
                    help="cut each name to this many characters")
    ap.add_argument("--known", default=None,
                    help="with --cut: also write the figures the "
                         "reduction gives on the cut (a test's answers)")
    ap.add_argument("--kernels", default="",
                    help="comma-separated kernel regexes for --known")
    ap.add_argument("--planes", default=None,
                    help="regex of planes to keep in the cut")
    args = ap.parse_args(argv)
    path = args.path
    if os.path.isdir(path):
        import glob
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        path = max(found, key=os.path.getmtime)
    print("trace:", path, os.path.getsize(path), "bytes")
    events = xplane.read_events(path)
    by_line: dict = {}
    for plane, line, name, start, dur in events:
        by_line.setdefault((plane, line), []).append((name, start, dur))
    t_min = min(e[3] for e in events)
    for (plane, line), evs in sorted(by_line.items()):
        lo = min(s for _, s, _ in evs)
        hi = max(s + d for _, s, d in evs)
        print(f"PLANE {plane!r} LINE {line!r}: {len(evs)} events, "
              f"{(lo - t_min) / 1e6:.3f}..{(hi - t_min) / 1e6:.3f} ms, "
              f"sum {sum(d for _, _, d in evs) / 1e6:.3f} ms")
        total: dict = {}
        for name, _, dur in evs:
            total[name] = total.get(name, 0.0) + dur
        for name, dur in sorted(total.items(),
                                key=lambda kv: -kv[1])[:args.top]:
            print(f"      {dur / 1e6:10.3f} ms  {name[:160]}")
    if args.cut:
        import re
        rx = re.compile(args.planes) if args.planes else None
        lo, hi = t_min + args.from_ms * 1e6, t_min + args.to_ms * 1e6
        kept = [(e[0], e[1], e[2][:args.name_width], e[3], e[4])
                for e in events if lo <= e[3] < hi
                and (rx is None or rx.search(e[0]))]
        with gzip.open(args.cut, "wt") as f:
            for e in kept:
                f.write(json.dumps(e) + "\n")
        print(f"cut {len(kept)} events into {args.cut}, "
              f"{os.path.getsize(args.cut)} bytes")
        if args.known:
            from benchmarks import trace_reduce as tr
            planes = tr.device_planes(kept)
            window = (lo, hi)
            got = tr.busy(kept, window)
            known = {"planes": planes, "window_ns": list(window),
                     "busy_s": got["busy_s"],
                     "idle_share": got["idle_share"], "kernels": {}}
            for pat in filter(None, args.kernels.split(",")):
                known["kernels"][pat] = tr.kernel_seconds(kept, pat,
                                                          window)
            if len(planes) > 1:
                known["collective"] = tr.collective_split(kept, window)
            with open(args.known, "w") as f:
                json.dump(known, f, indent=1)
            print("known:", json.dumps(known)[:600])
    return 0


if __name__ == "__main__":
    sys.exit(main())
