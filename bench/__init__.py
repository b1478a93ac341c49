"""The chip benchmark: cells, traffic, reference and metric readers.

`bench/run.py` is the one command; see `bench/harness.py`.
"""
