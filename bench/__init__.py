"""The benchmark: a data-driven harness over the D4M deployments (see run.py)."""
