"""Hand tools around the benchmark (not part of a run)."""
