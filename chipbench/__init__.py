"""Chip benchmark of the served streaming-GNN path (see ``run.py``)."""
