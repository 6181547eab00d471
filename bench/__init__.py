"""Chip benchmark of sparse-sync training (see ``bench/run.py``)."""
