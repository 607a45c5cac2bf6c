"""Chip benchmark of the federated round and the serving engine: one harness, cells as data files (see run.py)."""
