"""Chip benchmark of the fluid engine (see ``bench/run.py``)."""
