"""Chip benchmark of the DGNN-Booster serve path (see ``run.py``)."""
