"""Plain references, one module per model family, found by the name a
configuration file gives under ``reference``."""
