"""The on-chip serving benchmark: ``python3 bench/run.py --help``."""
