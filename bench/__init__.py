"""On-chip benchmark of the predictor: see ``bench/run.py``."""
