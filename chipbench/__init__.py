"""Chip benchmark of the Merlin reproduction: one cell, one run (see run.py)."""
