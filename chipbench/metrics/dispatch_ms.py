"""Executable dispatch (``core/summa.py``): mean duration of the window's
``repro.dispatch`` spans, in ms: JAX's and the runtime's enqueue of the
cached program.  Nothing to read where the program opens no such span."""
from __future__ import annotations


def read(run):
    lo, hi = run.window
    spans = [e for e in run.trace.spans("repro.dispatch") if lo <= e.start and e.end <= hi]
    if not spans:
        return None
    return 1e3 * sum(e.duration for e in spans) / len(spans)
