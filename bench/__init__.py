"""On-chip benchmark of the secure K-means system: see bench/run.py."""
