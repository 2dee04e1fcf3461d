"""Chip benchmark of the ECG solver: one cell per run, driven by the data
files in this directory (see ``run.py``)."""
