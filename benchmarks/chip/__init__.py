"""Chip benchmark: one cell, one run; see ``run_cell.py``."""
