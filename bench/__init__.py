"""On-chip benchmark of the bitmap-query server: see ``run.py``."""
