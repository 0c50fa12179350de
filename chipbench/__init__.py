"""The on-chip benchmark: one cell per run, `python chipbench/run.py`."""
