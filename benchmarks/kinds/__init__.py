"""Driver kinds, found by the name a workload file gives under ``kind``:
``run(ctx) -> record``."""
