"""The chip benchmark: one command runs one cell (see run.py)."""
