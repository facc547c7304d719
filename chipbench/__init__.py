"""Chip benchmark of the per-example gradient system (see harness.py)."""
