"""The benchmark harness: see run.py."""
