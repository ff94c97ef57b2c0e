"""On-chip benchmark of the HPCC suite: see ``bench/harness.py``."""
