"""Chip benchmark of the serving engine (see README.md)."""
