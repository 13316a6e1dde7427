"""Chip benchmark of the SUMMA engine (see ``chipbench/run.py``)."""
