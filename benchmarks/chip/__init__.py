"""Chip benchmark of the MIG scheduling system: cells, traffic and metrics read as data."""
