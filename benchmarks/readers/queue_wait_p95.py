"""Due -> ``prefill_start`` (the program's event, the benchmark's
clock), 95th percentile; a request that never started ranks slowest and
leaves nothing finite to report. ms."""
from benchmarks import stats


def read(rec, params):
    waits = [r.get("queue_wait_s") for r in rec.get("requests", [])]
    if not waits:
        return None
    p, finite = stats.latency_percentile(waits, 95)
    return 1e3 * p if finite else None
