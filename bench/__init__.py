"""Chip benchmark of the DWT engine (``bench/run.py`` is the entry)."""
