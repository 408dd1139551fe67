"""Share of the window the training loop spent waiting for input:
the program's ``HostInputTime`` scalars of the window's steps, over the
window's wall time on the benchmark's clock (host clock; %)."""


def read(rec, params):
    steps = [s for s in rec.get("steps", []) if "input_s" in s]
    elapsed = rec["window"]["t1"] - rec["window"]["t0"]
    if not steps or elapsed <= 0:
        return None
    return 100.0 * sum(s["input_s"] for s in steps) / elapsed
