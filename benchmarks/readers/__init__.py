"""Per-layer metric readers, found by the name a layer_metrics file gives
under ``reader``: ``read(record, params) -> float | dict | None``. A
reader that finds nothing to read returns None and the harness leaves the
metric out of the line."""
