"""Program adapters (config -> the program's model object, and its parameter tree -> the reference's named weights), one module per model family, found by the name a
configuration file gives under ``builder``."""
