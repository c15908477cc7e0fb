"""On-chip benchmark of the sort engine (see run.py)."""
