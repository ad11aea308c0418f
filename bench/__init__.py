"""The benchmark: cells, traffic, reference and metric readers (see run.py)."""
