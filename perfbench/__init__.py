"""Chip benchmark of the Tier J implicit BFS (see harness.py and run.py)."""
