"""On-chip benchmark of admission control; ``python3 bench/run.py --help``."""
