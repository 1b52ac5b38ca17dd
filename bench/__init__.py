"""On-chip benchmark harness: see bench/run.py."""
