"""The on-chip benchmark: one command runs one cell once (see run.py)."""
