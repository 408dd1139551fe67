"""How late the load generator ran: sent - due, 95th percentile over the
requests due in the window (benchmark clock; ms)."""
from benchmarks import stats


def read(rec, params):
    late = [r["sent_s"] - r["due_s"] for r in rec.get("requests", [])
            if r.get("sent_s") is not None]
    p = stats.percentile(late, 95)
    return None if p is None else 1e3 * p
