"""Live rows / ``max_batch``, averaged over the decode bursts of the
window (the program's ``decode`` events). %."""


def read(rec, params):
    bursts = [b for b in rec.get("bursts", []) if b["in_window"]]
    if not bursts:
        return None
    rows = sum(len(b["contexts"]) for b in bursts) / len(bursts)
    return 100.0 * rows / rec["max_batch"]
