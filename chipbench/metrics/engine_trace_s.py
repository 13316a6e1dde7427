"""SUMMA executor (``core/summa.py``): seconds the process spent in the
traced Python bodies of the engine's new executables
(``executable_cache_stats()["trace_s"]``), read after the window: the
engine's tracing share of set-up, as no executable is built inside a
window.  Nothing to read where the program keeps no such counter."""
from __future__ import annotations


def read(run):
    from repro.core import summa

    return summa.executable_cache_stats().get("trace_s")
