"""Chip benchmark of the medoid engine: one harness driven by data."""
