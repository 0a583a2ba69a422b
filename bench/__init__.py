"""On-chip benchmark of the serve path: one cell per run, see ``bench/run.py``."""
