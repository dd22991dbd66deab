"""Per-layer metric readers.  Each module has ``read(obs, **args)`` and
returns a number, or None where it finds nothing to read; a metric's own
file under ``metrics/`` names its reader and the arguments."""
