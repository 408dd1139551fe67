"""The on-chip benchmark of bigdl_tpu (BENCHMARK.json at the repo root).

Everything that decides a number lives here, where a PR that claims a
gain cannot change it: traffic generation, the reduction from traces and
events to metrics, the table of peaks, the FLOP and byte functions, the
plain references and the comparison that decides ``correct``. From the
program the benchmark takes only the system under test, its request
events, its summary scalars and its kernel names. See README.md.
"""
