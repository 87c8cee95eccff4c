"""Chip benchmark of Roomy's searches (see harness.py and run.py)."""
