"""Chip benchmark of the shifted randomized SVD (see ``run.py``)."""
