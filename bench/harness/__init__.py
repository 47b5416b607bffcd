"""The on-chip benchmark's harness: one run of one cell (see ``bench/run.py``)."""
