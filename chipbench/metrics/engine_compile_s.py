"""SUMMA executor (``core/summa.py``): seconds of the first calls of the
engine's new executables less their tracing (``build_s - trace_s`` of
``executable_cache_stats()``), read after the window: lowering, compiling
or loading from the compile cache, and the first enqueue; the engine's
compile share of set-up.  Nothing to read where the program keeps no such
counters."""
from __future__ import annotations


def read(run):
    from repro.core import summa

    stats = summa.executable_cache_stats()
    if "build_s" not in stats or "trace_s" not in stats:
        return None
    return stats["build_s"] - stats["trace_s"]
